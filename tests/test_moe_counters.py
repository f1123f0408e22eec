"""What the latent family's sparse layers count on the device reaches
the engine's books with the outputs a step already copies: ``moe_stats``
(``xllm_worker_moe_*_total``), the heartbeat's ``moe_dropped_tokens``
(true now: requested - computed, where the path used to report a
constant 0) and the step record's ``moe``."""

import json
import os


from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.runtime import engine as E
from xllm_service_tpu.runtime import worker as W
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "joyai-llm-flash")


def tiny_model():
    with open(os.path.join(CONFIG, "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CONFIG, "meta.json")) as f:
        cfg.update(json.load(f)["rehearsal_widths"])
    return ModelConfig.from_hf_config(cfg, "joyai-tiny"), cfg


def test_the_engine_books_what_the_sparse_layers_counted():
    mc, cfg = tiny_model()
    eng = E.Engine(mc, EngineConfig(
        page_size=16, num_pages=32, max_model_len=128, max_batch_size=2,
        prefill_buckets=(32,)))
    P, N = 21, 9
    eng.add_request(E.EngineRequest(
        request_id="r0", token_ids=list(range(3, 3 + P)),
        sampling=SamplingParams(temperature=0.0, max_tokens=N,
                                ignore_eos=True)))
    sampled, per_step = 0, []
    while eng.has_work():
        for out in eng.step():
            sampled += len(out.new_token_ids)
        per_step.append(dict(eng.last_step_moe))
    assert sampled == N
    k = cfg["num_experts_per_tok"]
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    st = eng.moe_stats
    # the prompt's rows once, then one row a decode iteration
    decoded = st["assignments"] // (k * sparse) - P
    assert st["assignments"] == (P + decoded) * k * sparse
    assert N - 1 <= decoded <= N
    assert st["dropped"] == 0 and eng.moe_dropped_tokens == 0
    assert eng.load_metrics()["moe_dropped_tokens"] == 0
    assert st["layers"] == (1 + decoded) * sparse
    # one row touches k experts of a layer; the prompt's 21 rows more
    assert st["experts_touched"] >= (1 + decoded) * k * sparse
    assert sum(s["assignments"] for s in per_step) == st["assignments"]
    rec = W._moe_record(per_step[-1])
    assert rec["dropped"] == 0 and rec["load_max_over_mean"] == 1.0
    assert W._moe_record(dict.fromkeys(st, 0)) is None


def test_a_dense_models_step_hands_back_the_one_scalar():
    from xllm_service_tpu.models import transformer
    assert transformer.moe_stats_shape(ModelConfig.tiny()) == ()
    assert transformer.moe_stats_shape(
        ModelConfig.tiny(num_experts=4)) == ()
    assert transformer.moe_stats_shape(tiny_model()[0]) == (5,)


def test_a_worker_exports_the_counters_and_records_the_steps_moe(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: the three counters on
    ``/metrics`` and ``moe`` in every step record that routed."""
    from http.client import HTTPConnection
    from chipbench import cluster
    from xllm_service_tpu.service.coordination import InMemoryStore
    _, cfg = tiny_model()
    model_dir = cluster.write_model_dir(str(tmp_path / "model"), cfg)
    w = W.Worker(W.WorkerOptions(model="joyai-tiny", model_dir=model_dir),
                 InMemoryStore()).start()
    try:
        host, port = w.name.rsplit(":", 1)

        def call(method, path, body=None):
            conn = HTTPConnection(host, int(port), timeout=300)
            try:
                conn.request(method, path, body=body, headers={
                    "Content-Type": "application/json"})
                r = conn.getresponse()
                return r.status, r.read().decode()
            finally:
                conn.close()

        prompt = " ".join(f"t{i}" for i in range(5, 25))
        status, _ = call("POST", "/v1/completions", json.dumps({
            "model": "joyai-tiny", "prompt": prompt, "max_tokens": 6,
            "temperature": 0.0, "ignore_eos": True}))
        assert status == 200

        def metric(name):
            return sum(float(ln.rsplit(" ", 1)[1])
                       for ln in call("GET", "/metrics")[1].splitlines()
                       if ln.startswith(name + "{"))

        k = cfg["num_experts_per_tok"]
        sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        st = w.primary_runtime().engine.moe_stats
        assert metric("xllm_worker_moe_assignments_total") \
            == st["assignments"] >= (20 + 5) * k * sparse
        assert metric("xllm_worker_moe_experts_touched_total") \
            == st["experts_touched"] > 0
        assert metric("xllm_worker_moe_dropped_assignments_total") == 0
        assert metric("xllm_worker_moe_dropped_tokens") == 0
        recs = [r["moe"] for r in w.steptrace.tail() if r["moe"]]
        assert sum(r["assignments"] for r in recs) == st["assignments"]
        assert all(r["dropped"] == 0 and r["load_max_over_mean"] >= 1
                   for r in recs)
    finally:
        w.stop()
