"""The dropless expert layer (parallel/expert.py ``dropless_moe``) against
an every-expert reference: whatever the routing, every assignment the
gate made is computed, none else, and the layer's own counts are the
hand counts. The bucketed path is shown to drop where this one cannot:
the reason the layer exists."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.parallel import expert

E, D, F, K = 16, 32, 24, 4


def weights(seed=0, layers=1):
    """The layers' stacks, as the layer takes them: [L, E, ...]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = (layers, E)
    return (jax.random.normal(ks[0], lead + (D, F)) * D ** -0.5,
            jax.random.normal(ks[1], lead + (D, F)) * D ** -0.5,
            jax.random.normal(ks[2], lead + (F, D)) * F ** -0.5)


def every_expert(x, topi, topw, valid, gate_w, up_w, down_w):
    """Each expert over each row, weighted by the gate's map."""
    wmap = np.zeros((x.shape[0], E), np.float32)
    for n in range(x.shape[0]):
        if valid[n]:
            for i, w in zip(np.asarray(topi[n]), np.asarray(topw[n])):
                wmap[n, i] += w
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", x, gate_w[0])) \
        * jnp.einsum("nd,edf->nef", x, up_w[0])
    return jnp.einsum("nef,efd,ne->nd", h, down_w[0], jnp.asarray(wmap))


def hand_counts(topi, valid):
    chosen = np.asarray(topi)[np.asarray(valid)]
    loads = np.bincount(chosen.reshape(-1), minlength=E)
    return {"dropped": 0, "assignments": int(loads.sum()),
            "experts_touched": int((loads > 0).sum()),
            "load_max": int(loads.max()) if loads.size else 0,
            "layers": int(loads.sum() > 0)}


def even(n):            # row r takes experts r*K .. r*K+K-1 (mod E)
    return (jnp.arange(n)[:, None] * K + jnp.arange(K)[None, :]) % E


ROUTINGS = {
    "even": (32, even(32), np.ones(32, bool)),
    "one_hot_expert": (32, jnp.broadcast_to(jnp.array([5, 1, 9, 12]),
                                            (32, K)), np.ones(32, bool)),
    "invalid_rows": (24, even(24), np.arange(24) % 3 != 1),
    "single_row": (1, even(1), np.ones(1, bool)),
    "nothing_valid": (8, even(8), np.zeros(8, bool)),
}


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_dropless_layer_computes_what_was_routed(name, kernel):
    n, topi, valid = ROUTINGS[name]
    x = jax.random.normal(jax.random.PRNGKey(1), (n, D))
    topw = jax.random.uniform(jax.random.PRNGKey(2), (n, K), minval=0.1)
    w = weights()
    out, stats = jax.jit(
        lambda *a: expert.dropless_moe(*a, layer=0, kernel=kernel,
                                       interpret=True))(
        x, topi, topw, jnp.asarray(valid), *w)
    want = every_expert(x, topi, topw, valid, *w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~valid].any()
    assert dict(zip(expert.MOE_STATS, np.asarray(stats).tolist())) \
        == hand_counts(topi, valid)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
def test_the_layers_whole_stack_is_read_at_the_layers_index(kernel):
    """Inside a layer scan the experts come as [L, E, ...] stacks with
    the traced layer index; that is the per-layer call."""
    n, topi, valid = ROUTINGS["invalid_rows"]
    x = jax.random.normal(jax.random.PRNGKey(3), (n, D))
    topw = jax.random.uniform(jax.random.PRNGKey(4), (n, K), minval=0.1)
    stack = weights(seed=7, layers=3)

    def scanned(x):
        def body(_, li):
            return None, expert.dropless_moe(
                x, topi, topw, jnp.asarray(valid), *stack, layer=li,
                kernel=kernel, interpret=True)
        return jax.lax.scan(body, None, jnp.arange(3, dtype=jnp.int32))[1]

    outs, stats = jax.jit(scanned)(x)
    for li in range(3):
        one, st = expert.dropless_moe(x, topi, topw, jnp.asarray(valid),
                                      *(w[li:li + 1] for w in stack),
                                      layer=0)
        np.testing.assert_allclose(np.asarray(outs[li]), np.asarray(one),
                                   rtol=1e-6, atol=1e-6)
        assert np.array_equal(np.asarray(stats[li]), np.asarray(st))


def test_the_bucketed_path_drops_where_every_row_takes_the_same_experts():
    """``moe_mlp`` at its serving default (capacity factor 2.0): an
    expert's bucket holds ``capacity`` rows of a group, the rest of the
    rows that chose it lose it. The dropless layer computes them all."""
    n, topi, valid = ROUTINGS["one_hot_expert"]
    x = jax.random.normal(jax.random.PRNGKey(5), (n, D))
    topw = jnp.full((n, K), 0.25)
    gates = jnp.zeros((n, E)).at[jnp.arange(n)[:, None], topi].set(topw)
    g, u, d = weights()
    _, dropped = expert.moe_mlp(
        x[None], jnp.zeros((D, E)), g[0], u[0], d[0], K, 2.0, group_size=512,
        norm_topk=False, gates=gates[None])
    cap = expert.capacity(n, E, K, 2.0)
    assert cap < n and int(dropped) == K * (n - cap) > 0
    _, stats = expert.dropless_moe(x, topi, topw, jnp.asarray(valid),
                                   g, u, d, layer=0)
    assert np.asarray(stats).tolist()[:4] == [0, n * K, K, n]


@pytest.mark.parametrize("bad", [E, E + 3, -1], ids=["E", "past_E", "minus_1"])
def test_an_assignment_no_expert_computes_is_counted_as_dropped(bad):
    """``dropped`` is read off what the matmul was given (the group each
    sorted row falls into against the expert the gate chose), so an
    expert id that names no expert shows: the rows it displaces too."""
    n, topi, valid = ROUTINGS["even"]
    x = jax.random.normal(jax.random.PRNGKey(6), (n, D))
    topw = jnp.full((n, K), 0.25)
    _, stats = expert.dropless_moe(
        x, topi.at[3, 1].set(bad), topw, jnp.asarray(valid), *weights(),
        layer=0)
    st = dict(zip(expert.MOE_STATS, np.asarray(stats).tolist()))
    assert st["dropped"] >= 1
    assert st["dropped"] + st["assignments"] == n * K
