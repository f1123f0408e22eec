"""HLO copy census: prove the KV pools never move, INCLUDING the
jit-call boundary.

Round 5 fixed the in-loop pool copies (aliased Pallas writers + layered
attention) and left one residue documented: XLA still copied the pools
a handful of times per CALL around the two custom calls, because the
opaque attention call read a buffer the post-scan writer aliased —
amortized to noise inside the fused 64-step decode burst, but
~10-15 GB per PREFILL call. Write-then-attend
(``KernelPlan.write_then_attend``; EngineConfig.write_then_attend /
XLLM_WRITE_THEN_ATTEND where an engine resolves it) removes the
hazard at the root: the aliased writer is the pool's first consumer in
every layer body, so nothing ever reads the pre-write buffer.

This tool is the ground truth for that claim: it AOT-compiles the
jitted serving programs for v5e (tools/aot_tpu.py — local libtpu, no
chip, CPU runtime pinned) and counts COPY instructions whose result is
pool-sized anywhere in the optimized HLO — loop bodies AND the entry
computation, i.e. the call boundary round 5's in-loop census could not
see. Expected with write_then_attend on: zero in the prefill program
and zero in the decode burst.

Run:  python tools/aot_copy_census.py            # bench shape, A/B
      python tools/aot_copy_census.py --tiny     # small shapes (fast)
      python tools/aot_copy_census.py --cells    # every cell's prefill

Prints one verdict line per (program, mode) plus a JSON summary. The
tier-1 suite runs the same census at the tiny shape
(tests/test_copy_census.py), so a PR reintroducing pool copies fails
CI instead of shipping a silent 10 GB/call regression.

A second census beside it (PR 44), ``census_weight_relayouts``: the
slices into a temporary and the copies into another layout of a
projection WEIGHT, which the compiler pays every step where a product's
result goes straight into a reshape to heads. The tier-1 suite compiles
the dense decode and prefill programs at the benchmark's two dense
cells' widths (``build_cell_programs`` under ``chip_plan``) and holds
them at zero, and with them the pool copies of the hybrid cell's
programs, which one form of the weights' cure brought in.

A third (PR 47), ``census_layer_results``: results that are ONE LAYER of
a pool, which the XLA prefill materialized once a layer a pool where its
attention read sliced the layer out in front of the gather. ``--cells``
(``run_cells_census``) compiles the prefill program of every cell of
``BENCHMARK.json`` at every shape its mix warms up and prints the three
verdicts a shape; the tier-1 suite holds three shapes a cell at zero.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.aot_tpu import aot_compile, sds  # noqa: E402  (pins CPU)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# "%copy.3 = bf16[16,512,128,8,64]{...} copy(...)" — async copies lower
# as copy-start/copy-done pairs whose copy-start result is a TUPLE
# "(bf16[...]{...}, u32[])"; count starts only, or one physical copy
# would tally twice. The opcode match anchors on "<space>opcode(" so
# copy-done / fusion metadata never match.
_SHAPE_RE = re.compile(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]")
_OP_RE = re.compile(r"\s(copy|copy-start)\(")


def census_pool_copies(hlo_text: str, pool_shape) -> list:
    """All copy/copy-start instructions in ``hlo_text`` whose result has
    exactly the pool's element count. Returns the matched shape strings
    (empty list = the pools never move).

    Copies into/out of an ALTERNATE memory space (an ``S(k)`` layout
    annotation, k != 0) are excluded: those are XLA's memory-space-
    assignment prefetches into faster memory — an optimization that only
    exists when the pool is toy-sized enough to fit — not the defensive
    HBM↔HBM pool copies this census hunts (which carry default-space
    layouts on both sides)."""
    want = 1
    for d in pool_shape:
        want *= int(d)
    hits = []
    for line in hlo_text.splitlines():
        op = _OP_RE.search(line)
        if not op:
            continue
        m = _SHAPE_RE.search(line)
        if not m:
            continue
        if re.search(r"S\([1-9]", line[:op.start()]):
            # The RESULT (destination) lives in alternate memory: a
            # prefetch, not a copy-out. A defensive copy's destination
            # is default-space even when its OPERAND was placed in
            # S(1) (that one must still count — the positive control's
            # aliased-output copy-back is exactly that shape).
            continue
        dims = m.group(1)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n == want:
            hits.append(f"{op.group(1)} {dims}")
    return hits


# "%f.6 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} fusion(...), kind=kLoop,
# calls=%fused_computation.82"; a copy-start's result is a tuple of the
# destination, the source and a context word.
_RELAYOUT_OP_RE = re.compile(r"\s(copy|copy-start|transpose|fusion)\(")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_LAYOUT_RE = re.compile(r"\]\{([0-9,]*)([^}]*)\}")


def census_weight_relayouts(hlo_text: str, weight_shapes) -> list:
    """Instructions of a compiled program that put a projection weight
    somewhere else before a product reads it: every ``copy`` /
    ``copy-start`` / ``transpose``, and every fusion that holds a
    ``dynamic-slice``, whose RESULT has the element count of one of
    ``weight_shapes`` (give each weight's stacked ``[L, D, N]`` and a
    layer's ``[D, N]``). Returns
    ``"<opcode> <dims> {<major-to-minor>}"`` strings; an empty list means
    every product reads its weight where it lies, as the feed-forward's
    do (their slice of the stack is fused INTO the product, whose result
    is an activation).

    A fusion's own body is not searched: its instructions are the
    fusion's arithmetic, and nothing in it is materialized. Unlike
    ``census_pool_copies`` a result in an alternate memory space
    (``S(1)``) COUNTS: the slice-then-relayout this census hunts lands
    there (PERF.md, PR 44: ``constant_dynamic-slice_fusion.6-8`` into
    ``S(1)``, then ``copy`` to ``{1,2,0}``), and it is paid every step
    whatever memory holds the temporary. The one thing excused is a
    ``copy-start`` into an alternate space that keeps the order of the
    dimensions: the prefetch of a whole unstacked weight, which is that
    weight's one read and no relayout (a leading layer outside the scan
    of a model whose layers differ in kind)."""
    want = {math.prod(shape) for shape in weight_shapes}
    lines = hlo_text.splitlines()
    # A first pass sorts the text's computations: which are fusion
    # bodies, and which hold a dynamic-slice, in themselves or in a
    # fusion nested in them.
    bodies, slicing, calls, name = set(), set(), {}, None
    for line in lines:
        head = _COMPUTATION_RE.match(line)
        if head:
            name = head.group(1)
            continue
        if " dynamic-slice(" in line:
            slicing.add(name)
        if " fusion(" in line:
            callee = _CALLS_RE.search(line)
            if callee:
                bodies.add(callee.group(1))
                calls.setdefault(name, set()).add(callee.group(1))
    grew = True
    while grew:
        grew = False
        for caller, callees in calls.items():
            if caller not in slicing and callees & slicing:
                slicing.add(caller)
                grew = True
    hits, name = [], None
    for line in lines:
        head = _COMPUTATION_RE.match(line)
        if head:
            name = head.group(1)
            continue
        op = _RELAYOUT_OP_RE.search(line)
        m = _SHAPE_RE.search(line)
        if (name in bodies or not op or not m or math.prod(
                int(d) for d in m.group(1).split(",") if d) not in want):
            continue
        # (major-to-minor, tiling and memory space) of each shape of the
        # result: a copy-start's are the destination's, then the source's.
        layouts = _LAYOUT_RE.findall(line[:op.start()])
        if op.group(1) == "fusion":
            callee = _CALLS_RE.search(line)
            if not callee or callee.group(1) not in slicing:
                continue
        elif (op.group(1) == "copy-start" and len(layouts) >= 2
              and re.search(r"S\([1-9]", layouts[0][1])
              and layouts[0][0] == layouts[1][0]):
            continue
        hits.append(f"{op.group(1)} {m.group(1)} "
                    f"{{{layouts[0][0] if layouts else ''}}}")
    return hits


# "%fusion.4 = bf16[768,128,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(...)":
# the opcode is the first word behind the "=" that whitespace precedes
# and a parenthesis follows (a layout's "T(8,128)" and "S(1)" follow
# none).
_OPCODE_RE = re.compile(r"=.*?\s([a-z][a-z\-]*)\(")
_NOT_MATERIALIZED = ("parameter", "bitcast", "get-tuple-element")


def _merges_to(dims, target) -> bool:
    """Can ``dims`` become ``target`` by merging adjacent axes?"""
    i = 0
    for want in target:
        have = 1
        while have < want and i < len(dims):
            have *= dims[i]
            i += 1
        if have != want:
            return False
    return i == len(dims)


def census_layer_results(hlo_text: str, pool_shapes) -> list:
    """Instructions of a compiled program whose RESULT is ONE LAYER of a
    pool (``pool.shape[1:]`` of each of ``pool_shapes``, as it is or with
    adjacent axes merged or split, size-1 axes aside: ``[1, P * ps, D]``
    is a layer of ``[L, P, ps, 1, D]``; an activation that merely has a
    layer's element count is not), whatever made it: a ``dynamic-slice``
    fusion, a ``copy``, a ``gather``. A prefill reads a few dozen pages
    of a layer and writes a window into it, so nothing in its program
    has cause to be as large as the layer: where the attention read
    slices a layer out of the pool in front of its gather, the compiler
    materializes the slice (PERF.md, PR 47:
    ``dynamic-slice_bitcast_fusion.4`` / ``.5`` of
    ``bf16[768,128,8,128]``, 201 MB written and read again once a layer a
    pool, half of the Mistral cell's prefill program). Returns
    ``"<opcode> <dims>"`` strings; an empty list means every read of a
    pool takes its pages where they lie.

    Not counted: ``parameter``, ``bitcast`` and ``get-tuple-element``
    (names for bytes that are there already), and a fusion's own body
    (its instructions are the fusion's arithmetic; what is materialized
    is the fusion's result, which counts). A result in an alternate
    memory space counts, as in ``census_weight_relayouts``."""
    layers = {tuple(d for d in shape[1:] if d != 1) for shape in pool_shapes}
    lines = hlo_text.splitlines()
    bodies = set()
    for line in lines:
        if " fusion(" in line:
            callee = _CALLS_RE.search(line)
            if callee:
                bodies.add(callee.group(1))
    hits, name = [], None
    for line in lines:
        head = _COMPUTATION_RE.match(line)
        if head:
            name = head.group(1)
            continue
        op = _OPCODE_RE.search(line)
        m = _SHAPE_RE.search(line)
        if (name in bodies or not op or not m
                or op.group(1) in _NOT_MATERIALIZED):
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d and d != "1")
        if any(_merges_to(layer, dims) or _merges_to(dims, layer)
               for layer in layers):
            hits.append(f"{op.group(1)} {m.group(1)}")
    return hits


def census_pools(hlo_text: str, pool_shapes, window=None) -> tuple:
    """(``census_layer_results``, ``census_pool_copies`` over every
    distinct pool shape) of one compiled program. ``window``: the (rows,
    tokens) of a prefill. A pool whose layer, its last axis aside, counts
    what the window's tokens count is left out of the layer census,
    which tells by shape and cannot tell the window's own activations
    from it (256 pages of rings [4, 5120] under a window of 1,024
    tokens over the mixer's 5,120 channels); its copies still count. A
    pool of no elements is no pool."""
    tokens = math.prod(window) if window else None
    # a pool of no layers (a kind of layer the model has none of) has no
    # bytes: nothing of it can be sliced or copied
    pool_shapes = [s for s in pool_shapes if math.prod(s)]
    return (census_layer_results(
        hlo_text, [s for s in pool_shapes if math.prod(s[1:-1]) != tokens]),
        [hit for pool in set(map(tuple, pool_shapes))
         for hit in census_pool_copies(hlo_text, pool)])


def _llama3_1b_sds():
    from xllm_service_tpu.config import ModelConfig
    cfg = ModelConfig.llama3_1b()
    L, Hq, Hkv, D = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    V, H, I = cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size
    bf = jnp.bfloat16
    layers = {
        "input_norm": sds((L, H), bf), "post_norm": sds((L, H), bf),
        "q_proj": sds((L, H, Hq * D), bf),
        "k_proj": sds((L, H, Hkv * D), bf),
        "v_proj": sds((L, H, Hkv * D), bf),
        "o_proj": sds((L, Hq * D, H), bf),
        "gate_proj": sds((L, H, I), bf), "up_proj": sds((L, H, I), bf),
        "down_proj": sds((L, I, H), bf),
    }
    params = {"embed": sds((V, H), bf), "final_norm": sds((H,), bf),
              "layers": layers}
    return cfg, params


def _tiny_sds():
    from xllm_service_tpu.config import ModelConfig
    # Small for compile speed but MOSAIC-ALIGNED: Hkv=8 sublanes and
    # D=64 lanes, matching the round-5 validated probe geometry
    # (docs/AOT_VERDICTS_r5.txt) — the test suite's tiny config (Hkv=2,
    # D=16) hits in-kernel [ps, Hkv, D] relayouts v5e Mosaic refuses to
    # lower, the same class round 3 hit in the V3 decode kernel.
    cfg = ModelConfig(name="tiny-census", vocab_size=256, hidden_size=128,
                      intermediate_size=256, num_layers=2, num_heads=16,
                      num_kv_heads=8, head_dim=64, rope_theta=10000.0,
                      max_position_embeddings=512, dtype="bfloat16")
    L, Hq, Hkv, D = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    V, H, I = cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size
    bf = jnp.bfloat16
    layers = {
        "input_norm": sds((L, H), bf), "post_norm": sds((L, H), bf),
        "q_proj": sds((L, H, Hq * D), bf),
        "k_proj": sds((L, H, Hkv * D), bf),
        "v_proj": sds((L, H, Hkv * D), bf),
        "o_proj": sds((L, Hq * D, H), bf),
        "gate_proj": sds((L, H, I), bf), "up_proj": sds((L, H, I), bf),
        "down_proj": sds((L, I, H), bf),
    }
    params = {"embed": sds((V, H), bf), "final_norm": sds((H,), bf),
              "layers": layers}
    return cfg, params


def build_programs(tiny: bool = False, plan=None):
    """(name → (fn, args, donate_argnums, pool_shape)) for the census:
    the prefill step, the single decode step, and the fused decode
    burst, under ``plan`` (default: ``census_plan(True)``), at the bench
    geometry (or a scaled-down structurally identical one for the
    tier-1 check)."""
    from xllm_service_tpu.models import transformer

    plan = plan or census_plan(True)
    if tiny:
        cfg, params = _tiny_sds()
        P, ps, burst = 32, 64, 4
        B, ctx, Bp, T = 4, 96, 4, 64
    else:
        cfg, params = _llama3_1b_sds()
        # The headline bench geometry: page_size 128, 512-page pool,
        # B=64 ctx=384 decode bursts of 64, one-call B=64 T=128 prefill.
        P, ps, burst = 512, 128, 64
        B, ctx, Bp, T = 64, 384, 64, 128
    L, Hkv, D = cfg.num_layers, cfg.kv_cache_heads, cfg.kv_cache_dim
    pool_shape = (L, P, ps, Hkv, D)
    kv = (sds(pool_shape, jnp.bfloat16), sds(pool_shape, jnp.bfloat16))

    def pow2(n):
        return 1 << max(n - 1, 0).bit_length()

    MP = pow2(-(-(ctx + 1) // ps))
    tok = sds((B,), jnp.int32)
    pos = sds((B,), jnp.int32)
    act = sds((B,), jnp.bool_)
    pt = sds((B, MP), jnp.int32)

    def decode_single(params, tok, pos, act, kv, pt):
        logits, kv = transformer.forward_decode(
            params, cfg, tok, pos, act, kv, pt, plan=plan)
        return jnp.argmax(logits, -1).astype(jnp.int32), kv

    def decode_burst(params, tok, pos, act, kv, pt):
        def body(carry, _):
            t, p, kv = carry
            logits, kv = transformer.forward_decode(
                params, cfg, t, p, act, kv, pt, plan=plan)
            t2 = jnp.argmax(logits, -1).astype(jnp.int32)
            return (t2, p + 1, kv), t2
        (t, p, kv2), toks = jax.lax.scan(
            body, (tok, pos, kv), None, length=burst)
        return toks, t, p, kv2

    MPp = pow2(-(-(T + 1) // ps))
    tokens = sds((Bp, T), jnp.int32)
    start = sds((Bp,), jnp.int32)
    lens = sds((Bp,), jnp.int32)
    ptp = sds((Bp, MPp), jnp.int32)

    def prefill_step(params, tokens, start, lens, kv, ptp):
        last, _, kv = transformer.forward_prefill(
            params, cfg, tokens, start, lens, kv, ptp, plan=plan)
        return jnp.argmax(last, -1).astype(jnp.int32), kv

    # The ragged mixed-batch program (XLLM_RAGGED_ATTN): same packed
    # [B, T]+(start, lens) surface as prefill but decode rows ride as
    # length-1 windows; always write-then-attend and never page-aligned
    # (the plan's mixed_program(), as engine.py builds _jit_ragged). The
    # pools must stay donated and unmoved exactly like the prefill
    # program they replace on mixed iterations.
    def ragged_step(params, tokens, start, lens, kv, ptp):
        last, _, kv = transformer.forward_prefill(
            params, cfg, tokens, start, lens, kv, ptp,
            plan=plan.mixed_program())
        return jnp.argmax(last, -1).astype(jnp.int32), kv

    return {
        "prefill": (prefill_step, (params, tokens, start, lens, kv, ptp),
                    (4,), pool_shape),
        "ragged": (ragged_step, (params, tokens, start, lens, kv, ptp),
                   (4,), pool_shape),
        "decode_single": (decode_single, (params, tok, pos, act, kv, pt),
                          (4,), pool_shape),
        "decode_burst": (decode_burst, (params, tok, pos, act, kv, pt),
                         (4,), pool_shape),
    }


def build_cell_programs(cfg, pages: int, table_width: int, batch: int = 8,
                        window: int = 256, page_size: int = 128,
                        state_slots: int = 0, plan=None,
                        prefill_rows: int = 1, window_pages: int = 0):
    """(name → (fn, args, jit keywords)), the shapes of the attention
    projections' weights, stacked and a layer's
    (``census_weight_relayouts``), and the pools' (``census_pool_copies``):
    the decode step and a prefill of ``prefill_rows`` windows of
    ``cfg``, a dense model, a latent one or one whose layers differ in
    kind, under the plan an engine
    resolves on a chip (``chip_plan``), pools donated and pinned
    row-major on both sides as an engine pins them. Shapes alone: nothing
    is allocated, so a cell's real widths cost a few seconds a program at
    a few layers. A model with a mixer beside attention takes
    ``state_slots`` slots of its fourth pool, and its programs the slot
    columns an engine hands them (a state row a decode row, four slot
    columns a prefill row). A model with window layers beside full ones
    takes ``window_pages`` pages of its second pair of pools, and its
    programs the two tables side by side."""
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.runtime.engine import (latent_pool_format,
                                                 row_major_format)

    plan = plan or chip_plan(cfg)
    there = sds((1,), jnp.int32).sharding

    def described(make):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=there),
            jax.eval_shape(make))
    params = described(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    kv = described(
        lambda: transformer.init_kv_cache(cfg, pages, page_size,
                                          state_slots=state_slots,
                                          window_pages=window_pages))
    table_width *= 2 if cfg.num_swa_layers else 1
    # ... but a latent model's one pool, which an engine on a TPU pins
    # with its size-1 head axis outermost (Engine._pool_format).
    pin = tuple(latent_pool_format(x.sharding) if cfg.mla
                else row_major_format(x.ndim, x.sharding) for x in kv)
    by_slot = cfg.num_state_layers > 0
    jit_kw = {"donate_argnums": (4,),
              "in_shardings": (None, None, None, None, pin, None)
              + ((None,) if by_slot else ()),
              "out_shardings": (None, pin)}

    def decode(params, tok, pos, act, kv, pt, rows=None):
        return transformer.forward_decode(
            params, cfg, tok, pos, act, kv, pt, plan=plan,
            state_rows=rows)[:2]

    def prefill(params, tokens, start, lens, kv, pt, cols=None):
        last, _, kv = transformer.forward_prefill(
            params, cfg, tokens, start, lens, kv, pt, plan=plan,
            state_cols=cols)[:3]
        return last, kv

    def ints(*shape):
        return sds(shape, jnp.int32)
    programs = {
        "decode": (decode, (params, ints(batch), ints(batch),
                            sds((batch,), jnp.bool_), kv,
                            ints(batch, table_width))
                   + ((ints(batch),) if by_slot else ()), jit_kw),
        "prefill": (prefill, (params, ints(prefill_rows, window),
                              ints(prefill_rows), ints(prefill_rows), kv,
                              ints(prefill_rows, table_width))
                    + ((ints(prefill_rows, 4),) if by_slot else ()),
                    jit_kw),
    }
    weights = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if getattr(path[-1], "key", None) in ("q_proj", "k_proj", "v_proj",
                                              "o_proj"):
            weights += [tuple(leaf.shape), tuple(leaf.shape[1:])]
    return programs, weights, [tuple(pool.shape) for pool in kv]


def chip_plan(cfg):
    """What ``KernelPlan.from_env`` resolves for ``cfg`` on one chip at
    the benchmark's page size and the default bucket ladder: the paged
    decode kernel (a latent model's own pair beside it), the in-place
    decode writer, write-then-attend, the grouped matmul where the
    experts are the dropless ones; prefill attention in XLA and its
    write a scatter (a
    64-token bucket under a page of 128 is not page-aligned); real
    Mosaic lowering."""
    from xllm_service_tpu.ops.plan import KernelPlan
    return KernelPlan(decode_attn=True, kv_writers=True,
                      write_then_attend=True, latent_decode=cfg.mla,
                      # but a model whose state lives by slot, whose
                      # engine starts every window on a page boundary
                      # ... or a second pool of window layers
                      page_aligned=cfg.num_state_layers > 0
                      or cfg.num_swa_layers > 0,
                      expert_gmm=cfg.dropless_experts,
                      ssm_decode=cfg.num_state_layers > 0, interpret=False)


def census_plan(write_then_attend: bool):
    """Real Mosaic lowering, with the kernel mix THIS toolchain lowers:
    the aliased KV writers (the aliasing story the census is about) +
    XLA attention. The baked jax's Mosaic is older than round 5's and
    rejects the attention kernels' in-kernel [ps, Hkv, D] relayouts
    ("transpose[permutation=(1,0,2)]" / 3D dots — see
    tools/aot_kernel_probes.py output on this image), so programs with
    the attention kernels on cannot compile offline here; XLA attention
    reads the same pool buffers, so the copy hazard under test —
    attention reading what the writer aliases — is identical. (What an
    engine resolves under XLLM_PALLAS=0 XLLM_PALLAS_KV=1
    XLLM_PALLAS_INTERPRET=0 with page-multiple buckets.)"""
    from xllm_service_tpu.ops.plan import KernelPlan
    return KernelPlan(kv_writers=True, write_then_attend=write_then_attend,
                      interpret=False)


def _kv_layout_kwargs(args, donate, n_out, kv_out=None):
    """The engine's boundary-layout pin (runtime/engine.py
    row_major_format): KV pools at default major-to-minor on BOTH
    sides of the jit. Without it XLA assigns the pool parameters an
    attention-biased layout while the aliased writer custom call needs
    the default — 4 full-pool conversion copies per call."""
    from xllm_service_tpu.runtime.engine import row_major_format
    kv_idx = donate[0]
    lay = tuple(row_major_format(x.ndim, x.sharding) for x in args[kv_idx])
    ins = [None] * len(args)
    ins[kv_idx] = lay
    outs = [None] * n_out
    outs[-1 if kv_out is None else kv_out] = lay
    return {"in_shardings": tuple(ins), "out_shardings": tuple(outs)}


_N_OUT = {"prefill": 2, "ragged": 2, "decode_single": 2,
          "decode_burst": 4}


def run_census(tiny: bool = False, modes=(True, False)) -> dict:
    """Compile each program per write_then_attend mode; returns
    {f"{name}[wta={mode}]": {"ok":, "pool_copies":, "hits": [...]}}."""
    results = {}
    for mode in modes:
        for name, (fn, args, donate, pool_shape) in \
                build_programs(tiny, census_plan(mode)).items():
            tag = f"{name}[wta={'on' if mode else 'off'}]"
            try:
                kw = _kv_layout_kwargs(args, donate, _N_OUT[name])
                compiled = aot_compile(fn, args, donate_argnums=donate,
                                       **kw)
                hits = census_pool_copies(compiled.as_text(), pool_shape)
                results[tag] = {"ok": True, "pool_copies": len(hits),
                                "hits": hits[:8]}
                print(f"{tag}: COMPILE OK  pool_copies={len(hits)}")
            except Exception as e:  # noqa: BLE001 — verdicts, not crashes
                msg = str(e).replace("\n", " ")[:300]
                results[tag] = {"ok": False, "error": msg}
                print(f"{tag}: FAIL: {msg}")
    return results


def run_cells_census(only=()) -> dict:
    """The prefill program of every cell of ``BENCHMARK.json`` at EVERY
    shape its mix warms up (batch, bucket, table width: what the
    compiler copies depends on all three; PERF.md, PR 47: a latent pool
    was copied whole from two rows a window on and not at one), the
    cell's own pool and its config as the benchmark runs it. Prints a
    line a shape; returns {cell: {shape: {"layer_sized": [...],
    "pool_copies": [...], "temp_gb": ...}}}.

    Run:  python tools/aot_copy_census.py --cells [<cell> ...]"""
    from chipbench import traffic
    from xllm_service_tpu.config import ModelConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    results = {}
    for cell in bench["workloads"]:
        if only and cell["name"] not in only:
            continue
        with open(os.path.join(root, files[cell["config"]])) as f:
            cfg = ModelConfig.from_hf_config(json.load(f), cell["config"])
        with open(os.path.join(root, "chipbench", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        eng = mix["engine"]
        rows = eng["max_batch_size"]
        # the slots an engine gives a pool of states (runtime/engine.py)
        slots = 1 + 3 * rows if cfg.num_state_layers else 0
        # ... and a pool of window layers
        from xllm_service_tpu.runtime.engine import window_pool_pages
        wpages = window_pool_pages(
            cfg.sliding_window, eng["page_size"], rows, rows,
            2048)[1] if cfg.num_swa_layers else 0
        for B, T, MP in traffic.warmup_shapes(
                mix, eng["page_size"])["prefill"]:
            programs, _, pools = build_cell_programs(
                cfg, eng["num_pages"], MP, rows, window=T,
                page_size=eng["page_size"], state_slots=slots,
                prefill_rows=B, window_pages=wpages)
            fn, args, jit_kw = programs["prefill"]
            compiled = aot_compile(fn, args, **jit_kw)
            layer_sized, copies = census_pools(compiled.as_text(), pools,
                                               window=(B, T))
            verdict = {
                "layer_sized": layer_sized, "pool_copies": copies,
                "temp_gb": round(compiled.memory_analysis()
                                 .temp_size_in_bytes / 1e9, 3)}
            results.setdefault(cell["name"], {})[f"B{B}xT{T}xmp{MP}"] = \
                verdict
            print(f"{cell['name']} prefill B{B}xT{T}xmp{MP}: {verdict}",
                  flush=True)
    return results


def main() -> int:
    if "--cells" in sys.argv:
        results = run_cells_census(
            [a for a in sys.argv[1:] if not a.startswith("--")])
        clean = all(not v["layer_sized"] and not v["pool_copies"]
                    for shapes in results.values() for v in shapes.values())
        print(json.dumps({"aot_target": "v5e:1x1 (local libtpu)",
                          "prefill_reads_pages_where_they_lie": clean}))
        return 0 if clean else 1
    tiny = "--tiny" in sys.argv
    results = run_census(tiny=tiny)
    on_clean = all(r["ok"] and r["pool_copies"] == 0
                   for t, r in results.items() if "[wta=on]" in t)
    print(json.dumps({"aot_target": "v5e:1x1 (local libtpu)",
                      "tiny": tiny,
                      "write_then_attend_zero_pool_copies": on_clean,
                      "results": results}))
    return 0 if on_clean else 1


if __name__ == "__main__":
    raise SystemExit(main())
