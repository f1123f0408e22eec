"""The step programs of the configuration the benchmark had before the
latent one arrived lower to the text they lowered to on the parent
(PR 33's method: sha256 of ``.lower().as_text()``; ``tests/pins/
step_programs_pr34.json`` was taken on commit 17f4a2a with this file's
``digests``, and taken again by PR 44, which changed the dense path's
text on purpose: two ``optimization_barrier``s a layer body, before and
after the q/k/v products' reshape to heads, nothing else, by the count
of every operation in the old and the new text; PR 46 took the two
interpreted DECODE digests again: the paged decode kernel folds a block
of pages a grid step, every other digest held). A change to the latent
family's path, the expert layer or the step statistics must not reach a
dense model's program: the Mistral cell is then measured on what it was
measured on."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PINS = os.path.join(HERE, "pins", "step_programs_pr34.json")
PLANS = ("xla", "kernels_interpreted")


def digests(plan: str):
    """{program: sha256 of its lowered text} for ``mistral-7b-v01`` at its
    rehearsal widths (the sliding window kept), page 128, batch 8, under
    the CPU's default plan (the XLA reference) or with the kernels on
    (interpreted): ``XLLM_PALLAS`` is read once, when the engine is
    built."""
    os.environ["XLLM_PALLAS"] = "1" if plan == "kernels_interpreted" else "0"
    import jax
    import jax.numpy as jnp
    from chipbench import spec
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.runtime import engine as E
    d = os.path.join(ROOT, "chipbench", "configs", "mistral-7b-v01")
    cfg = spec.load_json(os.path.join(d, "config.json"))
    cfg.update(spec.load_json(os.path.join(d, "meta.json"))
               ["rehearsal_widths"])
    eng = E.Engine(ModelConfig.from_hf_config(cfg, "mistral-7b-v01"),
                   EngineConfig(page_size=128, num_pages=64,
                                max_model_len=8192, max_batch_size=8))
    key, out = jax.random.PRNGKey(0), {}
    for B, T, mp in ((1, 2048, 16), (4, 256, 64)):
        st = eng._sampling_tensors([], B)
        bias = eng._batch_bias([], B, eng.cfg.vocab_size)
        out[f"prefill:B{B}xT{T}xmp{mp}"] = eng._jit_prefill.lower(
            eng.params, jnp.zeros((B, E._PREFILL_HDR + T + mp), jnp.int32),
            eng.kv, *st, key, None, None, None, *bias, None, T).as_text()
    B = eng.ecfg.max_batch_size
    st = eng._sampling_tensors([], B)
    bias = eng._batch_bias([], B, eng.cfg.vocab_size)
    for mp in (32, 64):
        out[f"decode:mp{mp}"] = eng._jit_decode.lower(
            eng.params, jnp.zeros((B, E._PACK_COLS + mp), jnp.int32),
            eng.kv, *st, key, None, *bias).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in out.items()}


@pytest.mark.parametrize("plan", PLANS)
def test_mistrals_step_programs_lower_to_the_parents_text(plan):
    # A process of its own: the plan is read from the environment when
    # the engine is built, and the kernels' switch must not leak.
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), plan], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT},
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    with open(PINS) as f:
        pins = json.load(f)["digests"][plan]
    assert json.loads(p.stdout.splitlines()[-1]) == pins


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    print(json.dumps(digests(sys.argv[1])))
