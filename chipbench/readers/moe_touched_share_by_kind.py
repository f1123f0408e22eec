"""``moe_step_stat``'s ``experts_touched_share`` for a configuration
whose layers differ in kind: experts that received a row in the window's
decode-only steps, over the experts there are (``experts_key`` of the
configuration x its sparse layers x steps), in per cent. The sparse
layers are those whose kind, by the configuration's own ``layer_kinds``
(``weights.py`` beside its ``config.json``), ends in ``+moe``.
``moe_step_stat`` itself counts them as ``num_hidden_layers -
first_k_dense_replace`` over ``n_routed_experts``, keys this family's
``config.json`` does not have.
"""

from chipbench import spec


def read(ctx, info):
    lo = ctx["open_t"] + ctx["wall_minus_mono"]
    hi = ctx["close_t"] + ctx["wall_minus_mono"]
    recs = [s["moe"] for s in ctx["steps"]
            if lo <= s.get("t_wall", 0.0) < hi and s.get("moe")
            and s.get("kind") == "decode"]
    if not recs:
        return None
    cfg = ctx["config"]
    sparse = sum(k.endswith("+moe")
                 for k in spec.load_weights(ctx["cell"]).layer_kinds(cfg))
    return 100.0 * sum(r["experts_touched"] for r in recs) / (
        int(cfg[info["experts_key"]]) * sparse * len(recs))
