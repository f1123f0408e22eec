"""Plain reference of a looped dense decoder (``OuroForCausalLM``): a
layer stack run ``total_ut_steps`` times over THE SAME weights.

With ``N`` = RMSNorm (``x * rsqrt(mean(x^2) + eps) * w``), one layer is

    a = Attn(N_in(x));           x = x + N_in2(a)
    m = W_down(silu(W_gate h) * (W_up h)),  h = N_post(x)
    x = x + N_post2(m)

a pre-norm decoder layer with a SECOND norm on each sublayer's output
before the residual add (the checkpoint's ``input_layernorm``,
``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``). ``Attn`` is causal softmax attention,
one key-value head a query head (or grouped, as the config says), no
bias, half-against-half rotation, scale ``head_dim ** -0.5``. The model:
``x_0 = E[tokens]`` (no embedding scale); for pass ``p = 0 .. P - 1``
the layers in order over the same weights, then ``x = N_final(x)``, and
that normed ``x`` is both what pass ``p + 1`` starts from and what the
exit gate reads: ``lambda_p = sigmoid(w_g . x + b_g)``, exit probability
``q_p = lambda_p * prod_{j<p}(1 - lambda_j)``, the last pass takes what
is left. A row leaves the loop at the first pass whose cumulative exit
probability reaches ``early_exit_threshold``; at the published threshold
of 1 that is the last pass (only there does the sum reach 1), so every
position runs every pass and ``logits = W_head N_final(x)`` after the
last. A lower threshold is refused: it is another model.

Float32, ``highest``, plain ``jax.numpy``, no cache, no batching, nothing
imported from the program. The helpers that every dense decoder shares
(``rms_norm``, ``rope``, the blocked causal attention) are the
grouped-query family's (``chipbench/reference/gqa_decoder.py``).

For ``chipbench/check.py``, which walks a flat list of layers: the model
is ``P x L`` layers, layer ``i`` holding the weights of ``i mod L``, and
the LAST layer of every pass but the final one is of kind ``pass_end``:
it carries ``final_norm`` among its leaves and ends with it. The final
pass's norm is ``logits``'s, as for every family. Nothing is handed on
from layer to layer (``carry`` stays ``None``): each pass attends over
its own keys and values, which a reference without a cache recomputes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference.gqa_decoder import (  # noqa: F401
    _attend, _blocks, embed, logits, mm_f32, rms_norm, rope)

KINDS = ("layer", "pass_end")


def _refuse_early_exit(cfg: Dict[str, Any]) -> None:
    if float(cfg.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError(
            "an early_exit_threshold under 1 lets rows leave the loop at "
            "different passes: this reference runs every pass")


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry, q_block: int = 512, row_block: int = 2048):
    """One layer of one pass over one whole sequence x [T, D] (float32);
    returns ``(x, None)``. A ``pass_end`` layer ends with the final norm."""
    if kind not in KINDS or carry is not None:
        raise ValueError(f"a looped dense decoder has the kinds {KINDS} "
                         f"and carries nothing: got {kind!r}")
    _refuse_early_exit(cfg)
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg.get("num_key_value_heads") or hq)
    dh = int(cfg.get("head_dim") or cfg["hidden_size"] // hq)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = x.shape[0]
    pos = jnp.arange(t)

    h = rms_norm(x, lp["input_layernorm"], eps)
    q = rope(mm(h, lp["q_proj"]).reshape(t, hq, dh), pos, theta)
    k = rope(mm(h, lp["k_proj"]).reshape(t, hkv, dh), pos, theta)
    v = mm(h, lp["v_proj"]).reshape(t, hkv, dh)
    att = _blocks(lambda q0, qb: _attend(qb, k, v, q0, hq, hkv, 0),
                  q, q_block)
    x = x + rms_norm(mm(att, lp["o_proj"]), lp["input_layernorm_2"], eps)

    def ffn(_, xb):
        h = rms_norm(xb, lp["post_attention_layernorm"], eps)
        act = jax.nn.silu(mm(h, lp["gate_proj"])) * mm(h, lp["up_proj"])
        xb = xb + rms_norm(mm(act, lp["down_proj"]),
                           lp["post_attention_layernorm_2"], eps)
        if kind == "pass_end":
            xb = rms_norm(xb, lp["final_norm"], eps)
        return xb

    return _blocks(ffn, x, row_block), None


def exit_gate(x_normed, params: Dict[str, Any], mm: Callable = mm_f32):
    """lambda [T] of one pass's normed states [T, D]."""
    return jax.nn.sigmoid(
        mm(x_normed, params["exit_gate_w"][:, None])[:, 0]
        + params["exit_gate_b"].astype(jnp.float32))


def exit_probabilities(lams: List[Any]):
    """q [T, P] from each pass's lambda [T]: the last pass takes what is
    left of the others, whatever its own gate says."""
    left = jnp.ones_like(lams[0])
    q = []
    for lam in lams[:-1]:
        q.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(q + [left], axis=-1)


def forward_with_exits(params: Dict[str, Any], tokens,
                       cfg: Dict[str, Any], mm: Callable = mm_f32
                       ) -> Tuple[Any, Any]:
    """(logits [T, V], exit probabilities [T, P]) of one whole sequence:
    the small-size entry the CPU tests use. ``params['layers']`` is the
    flat list of P x L per-layer dicts (a pass's last one holds
    ``final_norm`` in every pass but the final)."""
    eps = float(cfg["rms_norm_eps"])
    x = embed(jnp.asarray(tokens), params["embed"])
    lams = []
    for lp in params["layers"]:
        ends = "final_norm" in lp
        x, _ = layer(x, lp, cfg, mm, "pass_end" if ends else "layer", None)
        if ends:
            lams.append(exit_gate(x, params, mm))
    last = rms_norm(x, params["final_norm"], eps)
    lams.append(exit_gate(last, params, mm))
    if len(lams) != int(cfg["total_ut_steps"]):
        raise ValueError(f"{len(lams)} passes in the list of layers, "
                         f"{cfg['total_ut_steps']} in the config")
    return (logits(x, params["final_norm"], params["lm_head"], cfg, mm),
            exit_probabilities(lams))


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32):
    """Logits [T, V] of one whole sequence, every pass run."""
    return forward_with_exits(params, tokens, cfg, mm)[0]
