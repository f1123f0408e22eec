"""DeepSeek-V2 (multi-head latent attention) fidelity vs the torch
oracle — the same HF-written-files shape as tests/test_hf_parity.py.

The engine serves MLA from a LATENT paged pool (one KV "head" of
kv_lora_rank + qk_rope_head_dim per token) with the kv_b up-projections
absorbed into the query/output sides; these tests pin that this is
bit-for-bit the same math HF computes per-head (associativity), across
the V2-Lite shape (no q compression, greedy routing), the full-V2 shape
(q_lora + group-limited routing), dense-prefix layers, shared experts,
and paged decode.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import forward_prefill, init_kv_cache
from xllm_service_tpu.runtime.checkpoint import load_checkpoint
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

_BASE = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=48, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, head_dim=8,          # head_dim == qk_rope (rope dims)
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
    first_k_dense_replace=1, routed_scaling_factor=1.5,
    max_position_embeddings=512, rope_theta=10000.0,
    attn_implementation="eager")


def _make_hf(kind: str):
    torch.manual_seed({"lite": 0, "full": 1}[kind])
    if kind == "lite":
        # V2-Lite shape: no q compression, greedy top-k routing.
        cfg = transformers.DeepseekV2Config(**_BASE, q_lora_rank=None,
                                            topk_method="greedy")
    else:
        # Full V2 shape: q_lora + device-limited (grouped) routing.
        cfg = transformers.DeepseekV2Config(
            **_BASE, q_lora_rank=24, topk_method="group_limited_greedy",
            n_group=2, topk_group=1)
    return transformers.DeepseekV2ForCausalLM(cfg).float().eval()


def _load_ours(path):
    with open(os.path.join(path, "config.json"), encoding="utf-8") as f:
        cfg = ModelConfig.from_hf_config(json.load(f), name="dsv2")
    # Drop-free capacity (cf >= E/k) for EXACT oracle parity: the tiny
    # shapes concentrate routing (esp. with a biased V3 gate), and a
    # capacity drop is correct serving behavior but not bit-parity.
    cfg = dataclasses.replace(cfg, dtype="float32",
                              moe_capacity_factor=8.0)
    return cfg, load_checkpoint(path, cfg)


def _our_all_logits(cfg, params, prompt):
    T = len(prompt)
    pages = (T + 3) // 4 + 1
    kv = init_kv_cache(cfg, 64, 4, jnp.float32)
    pt = jnp.asarray([list(range(1, pages + 1))], jnp.int32)
    _, all_logits, _ = forward_prefill(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.asarray([T], jnp.int32), kv, pt,
        return_all_logits=True)
    return np.asarray(all_logits)[0]


@pytest.mark.parametrize("kind", ["lite", "full"])
def test_mla_logits_match_torch_oracle(tmp_path, kind):
    model = _make_hf(kind)
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    cfg, params = _load_ours(str(tmp_path))
    assert cfg.mla and cfg.kv_cache_heads == 1
    assert cfg.kv_cache_dim == 32 + 8
    assert cfg.first_k_dense_replace == 1 and cfg.n_shared_experts == 1
    if kind == "full":
        assert cfg.q_lora_rank == 24
        assert cfg.topk_method == "group_limited_greedy"

    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
    with torch.no_grad():
        ref = model(torch.tensor([prompt])).logits[0].numpy()
    ours = _our_all_logits(cfg, params, prompt)
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=5e-4)
    np.testing.assert_array_equal(ours.argmax(-1), ref.argmax(-1))


def test_deepseek_v3_logits_match_torch_oracle(tmp_path):
    """DeepSeek-V3 deltas over V2: sigmoid routing with the learned
    e_score_correction_bias shaping SELECTION only (combine weights are
    raw sigmoid scores, normalized, scaled), top-2-sum group scores, and
    q compression — per-position parity vs the torch oracle."""
    torch.manual_seed(5)
    cfg = transformers.DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, head_dim=8,
        n_routed_experts=8, num_experts_per_tok=2, n_group=2,
        topk_group=1, n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        max_position_embeddings=512, rope_theta=10000.0,
        attn_implementation="eager")
    model = transformers.DeepseekV3ForCausalLM(cfg).float().eval()
    # A zero bias would make the bias path untestable — randomize it.
    with torch.no_grad():
        for layer in model.model.layers[1:]:
            layer.mlp.gate.e_score_correction_bias.uniform_(-0.5, 0.5)
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    our_cfg, params = _load_ours(str(tmp_path))
    assert our_cfg.moe_scoring == "sigmoid" and our_cfg.mla
    assert our_cfg.norm_topk_prob and our_cfg.routed_scaling_factor == 2.5
    assert params["layers_moe"]["router_bias"].shape == (2, 8)
    assert float(np.abs(np.asarray(
        params["layers_moe"]["router_bias"])).max()) > 0

    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
    with torch.no_grad():
        ref = model(torch.tensor([prompt])).logits[0].numpy()
    ours = _our_all_logits(our_cfg, params, prompt)
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=5e-4)
    np.testing.assert_array_equal(ours.argmax(-1), ref.argmax(-1))


def test_deepseek_config_gating():
    """Real V3/R1 configs declare topk_method 'noaux_tc' — it maps to
    the grouped sigmoid selection; contradictory scoring_func values and
    unknown topk_methods refuse at load."""
    base = dict(model_type="deepseek_v3", vocab_size=256, hidden_size=64,
                intermediate_size=128, moe_intermediate_size=48,
                num_hidden_layers=3, num_attention_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
                n_group=2, topk_group=1)
    c = ModelConfig.from_hf_config(dict(base, topk_method="noaux_tc",
                                        scoring_func="sigmoid"))
    assert c.topk_method == "group_limited_greedy"
    assert c.moe_scoring == "sigmoid"
    with pytest.raises(ValueError, match="scoring_func"):
        ModelConfig.from_hf_config(dict(base, scoring_func="softmax"))
    with pytest.raises(ValueError, match="topk_method"):
        ModelConfig.from_hf_config(dict(base, topk_method="aux_tc"))
    v2 = dict(base, model_type="deepseek_v2")
    with pytest.raises(ValueError, match="scoring_func"):
        ModelConfig.from_hf_config(dict(v2, scoring_func="sigmoid"))


def test_mla_no_dense_prefix_loads(tmp_path):
    """first_k_dense_replace=0 (the HF default): every layer is MoE, the
    dense prefix stack is empty — load + forward still match torch."""
    torch.manual_seed(2)
    cfg = transformers.DeepseekV2Config(
        **{**_BASE, "first_k_dense_replace": 0}, q_lora_rank=None,
        topk_method="greedy")
    model = transformers.DeepseekV2ForCausalLM(cfg).float().eval()
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    our_cfg, params = _load_ours(str(tmp_path))
    assert our_cfg.first_k_dense_replace == 0
    assert params["layers"]["input_norm"].shape[0] == 0
    prompt = [9, 8, 7, 6, 5]
    with torch.no_grad():
        ref = model(torch.tensor([prompt])).logits[0].numpy()
    ours = _our_all_logits(our_cfg, params, prompt)
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=5e-4)


def test_mla_engine_greedy_matches_hf(tmp_path):
    """Full engine path: latent paged pool, continuous batching, decode
    via the absorbed single-kv-head attention — greedy continuation
    matches torch exactly."""
    model = _make_hf("lite")
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    cfg, params = _load_ours(str(tmp_path))

    prompt = [12, 250, 3, 77, 8, 1]
    steps = 10
    ids = torch.tensor([prompt])
    with torch.no_grad():
        for _ in range(steps):
            nxt = model(ids).logits[0, -1].argmax()
            ids = torch.cat([ids, nxt.view(1, 1)], dim=1)
    ref = ids[0, len(prompt):].tolist()

    eng = Engine(cfg, EngineConfig(
        page_size=4, num_pages=64, max_model_len=128, max_batch_size=2,
        max_prefill_tokens=64, prefill_buckets=(8, 16, 32, 64)),
        params=params)
    eng.add_request(EngineRequest(
        request_id="mla", token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=steps, temperature=0.0,
                                ignore_eos=True)))
    got = []
    for _ in range(200):
        if not eng.has_work():
            break
        for out in eng.step():
            got.extend(out.new_token_ids)
    assert got == ref


def test_mla_decode_kernel_gate_matches_reference(tmp_path, monkeypatch):
    """With the kernels on, absorbed-MLA decode goes through the paged
    decode kernel (Pallas interpreter on CPU) — greedy tokens must equal
    the XLA-reference serving path's."""
    model = _make_hf("lite")
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    cfg, params = _load_ours(str(tmp_path))

    prompt = [12, 250, 3, 77, 8, 1]
    steps = 8

    def run(kernel: bool):
        monkeypatch.setenv("XLLM_PALLAS", "1" if kernel else "0")
        eng = Engine(cfg, EngineConfig(
            page_size=4, num_pages=64, max_model_len=128,
            max_batch_size=2, max_prefill_tokens=64,
            prefill_buckets=(8, 16, 32, 64)), params=params)
        assert eng.plan.latent_decode is kernel
        eng.add_request(EngineRequest(
            request_id="mla", token_ids=list(prompt),
            sampling=SamplingParams(max_tokens=steps, temperature=0.0,
                                    ignore_eos=True)))
        got = []
        for _ in range(100):
            if not eng.has_work():
                break
            for out in eng.step():
                got.extend(out.new_token_ids)
        return got

    assert run(kernel=True) == run(kernel=False)
