"""Expert-parallel MoE dispatch: grouped top-k routing with capacity
buckets.

Round 1 ran *every* expert on *every* token (dense MoE); round 2 moved to
sparse GShard/Switch-style capacity buckets but materialized the
``dispatch``/``combine`` masks globally as ``[N, E, C]`` with
``C ≈ k·cf·N/E`` — i.e. ``k·cf·N²`` floats each, ~2 GB per layer call at
an 8k window (VERDICT r2 weak #4). This version restores the missing
GShard ingredient: the **group axis**. Tokens are processed in fixed-size
groups of ``G`` (ModelConfig.moe_group_size); each group routes into its
own ``[G, E, C_g]`` buckets with ``C_g = ceil(k·G·cf/E)``, so mask memory
is ``k·cf·G·N`` — linear in sequence length with a constant group factor
(~67 MB at 8k vs ~2 GB), and the group axis batches the expert einsums.

Everything is static-shaped and expressed as einsums contracting over the
token axis, so GSPMD partitions the expert axis over the mesh's ``ep``
axis purely from the weight shardings (parallel/sharding.py
_MOE_LAYER_RULES) — expert buckets land on the devices holding those
experts' weights, with XLA inserting the dispatch/combine collectives
(the all-to-all a hand-written MoE implements with NCCL).

Capacity semantics are now group-local: each expert accepts at most
``C_g`` tokens *per group*. Tokens routed past a full expert lose that
expert's contribution and renormalize over their surviving experts (the
residual stream still carries them) — the standard TPU MoE trade for
static shapes. ``cf ≥ E/k`` guarantees no drops in any group (then
``C_g ≥ G``), which the equivalence tests use; serving defaults to 2.0.
Dropped assignments are COUNTED and surfaced (``moe_mlp`` returns the
count; the engine accumulates it into load metrics/heartbeats) — quality
degradation under load must be visible, not silent.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def capacity(group_tokens: int, num_experts: int, k: int,
             factor: float) -> int:
    """Static per-expert bucket size for one group: ≥1, 8-aligned,
    ≤ group_tokens."""
    c = int(group_tokens * k * factor / num_experts) + 1
    c = -(-c // 8) * 8
    return min(c, group_tokens)


def topk_dispatch(gates: jnp.ndarray, k: int, cap: int,
                  valid: jnp.ndarray = None,
                  norm_topk: bool = True
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route each of one group's tokens to its top-``k`` experts with
    capacity ``cap``.

    gates: [G, E] router softmax (fp32); ``valid`` [G] bool masks padding
    / inactive-lane tokens OUT of routing entirely — they must not consume
    expert capacity or a real token's output would depend on how much
    padding shares its batch. Returns
    ``dispatch`` [G, E, C] float (0/1 token→bucket-slot assignment) and
    ``combine`` [G, E, C] float (dispatch × renormalized routing weight).
    Bucket slots fill in token order (position = running count of earlier
    tokens choosing the same expert — the GShard cumsum trick).
    """
    N, E = gates.shape
    topv, topi = jax.lax.top_k(gates, k)                     # [N, k]
    if norm_topk:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros((N, E, cap), jnp.float32)
    combine = jnp.zeros((N, E, cap), jnp.float32)
    for j in range(k):                                       # k is tiny/static
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)  # [N, E]
        if valid is not None:
            oh = oh * valid.astype(jnp.int32)[:, None]
        pos = jnp.cumsum(oh, axis=0) - oh + counts[None, :]  # [N, E]
        counts = counts + jnp.sum(oh, axis=0)
        pos_j = jnp.sum(pos * oh, axis=1)                    # [N]
        keep = pos_j < cap
        slot = jax.nn.one_hot(jnp.where(keep, pos_j, cap), cap,
                              dtype=jnp.float32)             # [N, C]
        d_j = oh.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + topv[:, j][:, None, None] * d_j
    if norm_topk:
        # Renormalize over surviving experts so a token that lost one
        # expert to capacity doesn't shrink toward zero. (Un-normalized
        # routing — Qwen3-MoE norm_topk_prob=false — keeps raw softmax
        # weights; a capacity drop just loses that contribution, since
        # dividing by the survivor sum would force normalization.)
        w = jnp.sum(combine, axis=(1, 2), keepdims=True)     # [N, 1, 1]
        combine = jnp.where(w > 0, combine / jnp.maximum(w, 1e-9),
                            combine)
    return dispatch, combine


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, gate_w: jnp.ndarray,
            up_w: jnp.ndarray, down_w: jnp.ndarray, k: int,
            capacity_factor: float = 2.0,
            valid: jnp.ndarray = None,
            group_size: int = 512,
            norm_topk: bool = True,
            gates: jnp.ndarray = None,
            expert_style: str = "swiglu",
            gate_b: jnp.ndarray = None, up_b: jnp.ndarray = None,
            down_b: jnp.ndarray = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse SwiGLU MoE layer, group-chunked.

    x: [B, T, D]; router_w [D, E]; gate/up [E, D, F]; down [E, F, D];
    ``valid`` [B, T] bool marks real tokens (padding / inactive lanes are
    excluded from routing so they never take capacity from real tokens).
    Tokens flatten to [N, D], pad up to a multiple of ``group_size``
    (padding is invalid → routes nowhere), and dispatch group-by-group;
    the group axis rides the expert einsums as a batch dimension. Returns
    ``(out [B, T, D], dropped)`` where ``dropped`` (int32 scalar) counts
    the (token, expert) assignments lost to capacity this call.
    """
    B, T, D = x.shape
    N = B * T
    E = router_w.shape[-1]
    xf = x.reshape(N, D)
    vf = (jnp.ones((N,), bool) if valid is None
          else valid.reshape(N).astype(bool))
    G = min(group_size, N)
    pad = (-N) % G
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        vf = jnp.pad(vf, (0, pad))
    n_g = (N + pad) // G
    xg = xf.reshape(n_g, G, D)
    vg = vf.reshape(n_g, G)
    if gates is None:
        gates = jax.nn.softmax((xg @ router_w).astype(jnp.float32),
                               axis=-1)
    else:
        # Caller-selected routing map [B, T, E] (DeepSeek's grouped gate
        # with its scaling already applied): exactly k experts carry
        # nonzero weight per token, so top_k re-selects them and the
        # weights ride into combine unchanged (norm_topk must be False).
        gf = gates.reshape(N, -1).astype(jnp.float32)
        if pad:
            gf = jnp.pad(gf, ((0, pad), (0, 0)))
        gates = gf.reshape(n_g, G, -1)
    cap = capacity(G, E, k, capacity_factor)
    dispatch, combine = jax.vmap(
        lambda g, v: topk_dispatch(g, k, cap, v, norm_topk))(gates, vg)
    de = dispatch.astype(x.dtype)                        # [g, G, E, C]
    x_e = jnp.einsum("gnd,gnec->gecd", xg, de)           # [g, E, C, D]
    hg = jnp.einsum("gecd,edf->gecf", x_e, gate_w)
    hu = jnp.einsum("gecd,edf->gecf", x_e, up_w)
    if gate_b is not None:
        hg = hg + gate_b[None, :, None, :]
    if up_b is not None:
        hu = hu + up_b[None, :, None, :]
    if expert_style == "gptoss":
        # GPT-OSS clamped GLU: gate <= 7, up in [-7, 7],
        # (up + 1) * gate * sigmoid(1.702 * gate).
        hg = jnp.clip(hg, None, 7.0)
        hu = jnp.clip(hu, -7.0, 7.0)
        h = (hu + 1.0) * (hg * jax.nn.sigmoid(1.702 * hg))
    else:
        h = jax.nn.silu(hg) * hu
    y_e = jnp.einsum("gecf,efd->gecd", h, down_w)        # [g, E, C, D]
    if down_b is not None:
        # Per-expert output bias combines with the routing weight like
        # the rest of the expert output (weights sum to the router's
        # normalization, so the bias share rides the same combine).
        y_e = y_e + down_b[None, :, None, :]
    out = jnp.einsum("gecd,gnec->gnd", y_e, combine.astype(x.dtype))
    out = out.reshape(-1, D)[:N].reshape(B, T, D)
    # Every valid token requests exactly k experts; whatever didn't land
    # in a bucket was capacity-dropped.
    requested = k * jnp.sum(vf.astype(jnp.int32))
    kept = jnp.sum(dispatch).astype(jnp.int32)
    return out, requested - kept


# ---------------------------------------------------------------------------
# The dropless layer (the latent family's; PERF.md, PR 36). No buckets:
# every (row, expert) assignment the gate made is computed, and nothing
# else. The assignments are sorted by expert, each projection is ONE
# grouped matmul over the sorted rows (an expert's rows are contiguous,
# an expert without rows is never visited), and the rows go back to their
# places weighted by the gate. Work and weight traffic follow the routing:
# rows x k expert MLPs, and only the experts that received a row.
# ---------------------------------------------------------------------------

# What ``dropless_moe`` counts, in the order of its int32 stats vector.
MOE_STATS: Tuple[str, ...] = (
    "dropped",          # assignments the gate made and no expert computed
    "assignments",      # (valid row, expert) pairs computed
    "experts_touched",  # experts that received at least one row
    "load_max",         # rows of the most loaded expert
    "layers",           # 1 where the layer had a valid row (sums to calls)
    "elsewhere",        # assignments to experts another chip holds (the
                        # vector has it under a held share alone)
)


def dropless_moe(x: jnp.ndarray, topi: jnp.ndarray, topw: jnp.ndarray,
                 valid: jnp.ndarray, gate_w: jnp.ndarray, up_w: jnp.ndarray,
                 down_w: jnp.ndarray, layer: jnp.ndarray,
                 kernel: bool = False, interpret: bool = False,
                 first_held: int = 0, routed: int = 0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed SwiGLU experts without capacity.

    x [N, D]; ``topi`` / ``topw`` [N, k]: the experts the gate chose for
    each row and their weights (the choice is the gate's; nothing here
    re-selects); ``valid`` [N] bool: padding and inactive lanes, which are
    given to NO group; gate/up [L, E, D, F], down [L, E, F, D]: the
    layers' WHOLE stacks, and ``layer`` the (traced) index of this one.
    Returns ``(out [N, D], stats int32[len(MOE_STATS)])`` (without the
    last, ``elsewhere``, where every expert is held). ``kernel``
    takes the Pallas grouped matmul (ops/pallas/grouped_matmul.py;
    ``interpret`` anywhere but a TPU), else XLA's ``jax.lax.ragged_dot``:
    the plan's choice (ops/plan.py ``expert_gmm``).

    The stacks and not a layer's slice: the grouped matmul is a kernel
    call, whose operand a layer scan's slice would first be copied into
    (2.4 GB a layer at 256 experts of 2048 x 768; PERF.md, PR 36). The
    stack is read as L x E groups of which only this layer's E have rows.

    ``dropped`` is counted from what the matmul was GIVEN: a sorted row
    is computed where the group its position falls into, by the group
    sizes handed to the matmul, is the expert the gate chose for it. An
    expert id outside [0, E), a row past the last group, or sizes that
    disagree with the sort would show there.

    A held SHARE (``routed`` > E: the gate chose among ``routed``
    experts, of which this chip holds the E from ``first_held`` on): an
    assignment to an expert held elsewhere gets no group either, is
    computed by nobody here and adds nothing, so ``out`` is this chip's
    PART of the routed sum; it is counted as ``elsewhere`` and not as
    ``dropped``, which stays "the gate chose it, this chip holds it, no
    group computed it".
    """
    N, D = x.shape
    k = topi.shape[-1]
    L, E = gate_w.shape[:2]
    # An invalid row's assignments carry expert id E: they sort
    # behind every real group and belong to none.
    flat = jnp.where(valid[:, None], topi, E)
    if routed > E:
        here = (topi >= first_held) & (topi < first_held + E)
        elsewhere = jnp.sum((valid[:, None] & ~here).astype(jnp.int32))
        held = valid[:, None] & here
        flat = jnp.where(held, topi - first_held, E)
    flat = flat.reshape(N * k)
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), group_sizes, (layer * E,))
    gate_w, up_w, down_w = (w.reshape((L * E,) + w.shape[2:])
                            for w in (gate_w, up_w, down_w))
    if kernel:
        from xllm_service_tpu.ops.pallas.grouped_matmul import (
            grouped_matmul)

        def gmm(a, w):
            return grouped_matmul(a, w, groups, interpret=interpret)
    else:
        def gmm(a, w):
            return jax.lax.ragged_dot(a, w, groups)
    xs = x[order // k]                                   # [N*k, D]
    h = jax.nn.silu(gmm(xs, gate_w)) * gmm(xs, up_w)
    ys = gmm(h, down_w)                                  # [N*k, D]
    # Back to (row, choice) order; rows past the last group are no
    # expert's output, whatever the matmul left there.
    inv = jnp.zeros((N * k,), order.dtype).at[order].set(
        jnp.arange(N * k, dtype=order.dtype))
    y = ys[inv].reshape(N, k, D)
    if routed > E:
        w = jnp.where(held, topw, 0.0).astype(x.dtype)       # [N, k]
        y = jnp.where(held[:, :, None], y, jnp.zeros((), y.dtype))
    else:
        w = jnp.where(valid[:, None], topw, 0.0).astype(x.dtype)
        y = jnp.where(valid[:, None, None], y, jnp.zeros((), y.dtype))
    out = jnp.einsum("nkd,nk->nd", y, w,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    # The group each sorted position falls into (L * E past the last).
    given = jnp.searchsorted(jnp.cumsum(groups),
                             jnp.arange(N * k, dtype=jnp.int32),
                             side="right", method="compare_all")
    chosen = flat[order]
    computed = jnp.sum(((chosen >= 0) & (chosen < E)
                        & (given == layer * E + chosen))
                       .astype(jnp.int32))
    requested = k * jnp.sum(valid.astype(jnp.int32))
    stats = [requested - computed, computed,
             jnp.sum((group_sizes > 0).astype(jnp.int32)),
             jnp.max(group_sizes), (computed > 0).astype(jnp.int32)]
    if routed > E:
        stats[0] = stats[0] - elsewhere     # asked of another chip
        stats.append(elsewhere)
    return out, jnp.stack(stats)
