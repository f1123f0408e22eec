"""Cross-PROCESS KV device wire (runtime/kv_wire.py, SURVEY.md §5.8).

The in-process PD tests exercise the transfer-server wire over loopback,
but the reference's PD data plane runs between engine *processes*
(SURVEY.md §2.3: NCCL between engine clusters; the service only brokers
addresses). This test proves that shape for real — two worker OS
processes, a master process's front door, KV pulled device-to-device by
the decode process from the prefill process's transfer server. It exists
because the same-process tests CANNOT catch cross-process transport
bugs: the PJRT server without a TCP bulk-transport address serves
loopback pulls fine and hard-aborts (CHECK failure) on remote ones.
"""

import http.client
import os
import queue
import re
import subprocess
import sys
import threading
import time

import pytest

from xllm_service_tpu.service.coordination_net import StoreServer
from xllm_service_tpu.service.httpd import http_json


def _metrics(addr: str) -> str:
    conn = http.client.HTTPConnection(addr, timeout=10)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    return text


def test_cross_process_device_wire_migration():
    env = dict(os.environ, PYTHONPATH=os.getcwd(), JAX_PLATFORMS="cpu")
    store_srv = StoreServer().start()
    procs = []
    stderr_tail: list = []
    try:
        master = subprocess.Popen(
            [sys.executable, "-m", "xllm_service_tpu.service.master",
             "--host", "127.0.0.1", "--http-port", "0", "--rpc-port", "0",
             "--etcd-addr", store_srv.address,
             "--heartbeat-interval", "0.3",
             "--master-upload-interval", "0.3"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        procs.append(master)
        http_addr = None
        deadline = time.monotonic() + 60
        for ln in master.stdout:
            if ln.startswith("XLLM_SERVICE_UP"):
                f = dict(kv.split("=", 1) for kv in ln.split()[1:])
                http_addr, rpc_addr = f["http"], f["rpc"]
                break
            assert time.monotonic() < deadline, "master boot timeout"
        assert http_addr, "master never announced"

        lines: "queue.Queue" = queue.Queue()

        def spawn_worker(itype: str) -> subprocess.Popen:
            code = ("from xllm_service_tpu.runtime.worker import main; "
                    f"main(['--instance-type','{itype}',"
                    f"'--service-addr','{rpc_addr}',"
                    f"'--store-addr','{store_srv.address}',"
                    "'--page-size','16','--num-pages','64',"
                    "'--max-model-len','256','--max-batch-size','4',"
                    "'--heartbeat-interval-s','0.3'])")
            p = subprocess.Popen([sys.executable, "-c", code],
                                 stdout=subprocess.DEVNULL,
                                 stderr=subprocess.PIPE, text=True,
                                 env=env)

            def reader() -> None:
                for ln in p.stderr:
                    stderr_tail.append(f"[{itype}] {ln.rstrip()}")
                    del stderr_tail[:-100]
                    lines.put((itype, ln))
                lines.put((itype, None))

            threading.Thread(target=reader, daemon=True).start()
            return p

        procs.append(spawn_worker("PREFILL"))
        procs.append(spawn_worker("DECODE"))

        waddr: dict = {}
        deadline = time.monotonic() + 240
        while len(waddr) < 2 and time.monotonic() < deadline:
            try:
                tag, ln = lines.get(timeout=5)
            except queue.Empty:
                continue
            assert ln is not None, \
                f"{tag} died at boot:\n" + "\n".join(stderr_tail)
            mm = re.search(r"worker (\S+:\d+) serving", ln)
            if mm:
                waddr[tag] = mm.group(1)
        assert len(waddr) == 2, f"workers never announced: {waddr}"

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if "xllm_service_instances 2" in _metrics(http_addr):
                break
            time.sleep(0.3)
        else:
            raise TimeoutError("workers never registered at master")

        status, resp = http_json(
            "POST", http_addr, "/v1/completions",
            {"model": "tiny", "prompt": "cross process device wire",
             "max_tokens": 6, "temperature": 0.0, "ignore_eos": True},
            timeout=300.0)
        assert status == 200, (resp, stderr_tail[-30:])
        assert resp["usage"]["completion_tokens"] == 6

        wm = _metrics(waddr["PREFILL"])
        assert "xllm_worker_kv_migration_device_wire_total 1" in wm, \
            [ln for ln in wm.splitlines() if "migration" in ln]
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        store_srv.stop()
