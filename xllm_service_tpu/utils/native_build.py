"""On-demand build of the native artefacts under ``csrc/``.

One artefact per source file, named by a hash of what it was built from:
``build/native/<stem>-<sha256 of source text and compiler flags, 16 hex
digits><suffix>``. An artefact is reused only when that exact name
exists, so a leftover from older source can never be loaded for newer
source — whatever its mtime says (a copied tree has meaningless mtimes,
and ``build/`` is not committed, so a fresh checkout always builds).
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Optional, Sequence

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def artifact_path(src_name: str, stem: str, suffix: str,
                  flags: Sequence[str]) -> Optional[str]:
    """Where the artefact for the CURRENT text of ``csrc/<src_name>``
    lives (built or not); None when the source is not there."""
    src = os.path.join(_ROOT, "csrc", src_name)
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read())
    except OSError:
        return None
    digest.update("\0".join(flags).encode())
    return os.path.join(_ROOT, "build", "native",
                        f"{stem}-{digest.hexdigest()[:16]}{suffix}")


def build_artifact(src_name: str, stem: str, suffix: str,
                   flags: Sequence[str],
                   timeout_s: float = 180.0) -> Optional[str]:
    """Path of the built artefact for ``csrc/<src_name>``, compiling it
    first if its hash-named file is not there yet. None when there is no
    source or no working toolchain — each caller has a designed,
    equivalent Python path for that case and says which one served."""
    out = artifact_path(src_name, stem, suffix, flags)
    if out is None or os.path.exists(out):
        return out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # Compile to a process-unique temp name and rename atomically so a
    # concurrent process can never load a partially written artefact.
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [os.environ.get("CXX", "g++"), *flags,
           os.path.join(_ROOT, "csrc", src_name), "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True,
                       timeout=timeout_s)
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return out
