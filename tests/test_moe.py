"""Expert parallelism (parallel/moe.py): all_to_all dispatch over an
``expert`` mesh axis must match a per-token dense reference when capacity
is ample, drop deterministically when it is not, and train end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import make_mesh, moe_apply

E, T, D = 4, 16, 8  # experts (one per device), tokens per device, d_model


def expert_fn(p, x):
    return jnp.tanh(x @ p["w"]) * p["scale"]


def _setup(seed=0):
    rng = np.random.RandomState(seed)
    # Stacked expert params: leading axis = number of experts.
    params = {
        "w": jnp.asarray(rng.randn(E, D, D) * 0.5, jnp.float32),
        "scale": jnp.asarray(1.0 + rng.rand(E, 1), jnp.float32),
    }
    x = jnp.asarray(rng.randn(E, T, D), jnp.float32)       # per-device tokens
    logits = jnp.asarray(rng.randn(E, T, E), jnp.float32)  # per-device gates
    return params, x, logits


def _dense_reference(params, x, logits, k, capacity_factor):
    """Per-token loop on the host, including capacity dropping in the same
    slot-filling order."""
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    x = np.asarray(x)
    # GShard convention: capacity scales with k (top-k emits k*T assignments).
    capacity = max(int(np.ceil(T * k * capacity_factor / E)), k)
    out = np.zeros_like(x)
    fill = np.zeros(E, np.int64)
    chosen = [[] for _ in range(T)]  # (expert, gate, kept)
    avail = np.ones((T, E))
    for _ in range(k):
        masked = np.where(avail > 0, probs, -np.inf)
        for t in range(T):
            e = int(np.argmax(masked[t]))
            kept = fill[e] < capacity
            fill[e] += 1 if kept else 0
            chosen[t].append((e, probs[t, e], kept))
            avail[t, e] = 0.0
    # Slot order matches moe_apply: rounds outer, tokens in order (cumsum).
    for t in range(T):
        gates = [g for _, g, _ in chosen[t]]
        norm = sum(gates) if k > 1 else 1.0
        for e, g, kept in chosen[t]:
            if kept:
                p_e = {kk: np.asarray(v[e]) for kk, v in params.items()}
                y = np.tanh(x[t] @ p_e["w"]) * p_e["scale"]
                out[t] += (g / norm) * y
    return out


def _run_moe(params, x, logits, k, capacity_factor):
    mesh = make_mesh({"expert": E}, devices=jax.devices()[:E])

    def body(p, xx, gg):
        # xx/gg arrive as this device's [1, T, .] slice of the stacked
        # per-device arrays.
        y, aux = moe_apply(expert_fn, p, xx[0], gg[0], axis_name="expert",
                           capacity_factor=capacity_factor, num_selected=k)
        return y[None], aux[None]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert")),
        out_specs=(P("expert"), P("expert")),
        check_vma=False))
    y, aux = f(params, x, logits)
    return np.asarray(y), np.asarray(aux)


def test_moe_top1_matches_dense_reference_ample_capacity():
    params, x, logits = _setup()
    y, _ = _run_moe(params, x, logits, k=1, capacity_factor=float(E))
    for dev in range(E):
        ref = _dense_reference(params, x[dev], logits[dev], 1, float(E))
        np.testing.assert_allclose(y[dev], ref, rtol=1e-5, atol=1e-5)


def test_moe_top2_matches_dense_reference_ample_capacity():
    params, x, logits = _setup(seed=1)
    y, _ = _run_moe(params, x, logits, k=2, capacity_factor=float(E))
    for dev in range(E):
        ref = _dense_reference(params, x[dev], logits[dev], 2, float(E))
        np.testing.assert_allclose(y[dev], ref, rtol=1e-5, atol=1e-5)


def test_moe_capacity_drops_tokens():
    params, x, logits = _setup(seed=2)
    # Route every token to expert 0: with capacity ceil(T*0.25/E)=1 only one
    # token per device survives.
    logits = jnp.zeros_like(logits).at[:, :, 0].set(10.0)
    y, _ = _run_moe(params, x, logits, k=1, capacity_factor=0.25)
    for dev in range(E):
        nonzero = np.abs(y[dev]).sum(axis=-1) > 1e-9
        assert nonzero.sum() == 1, nonzero
        assert nonzero[0]  # slot-filling keeps the earliest token


def test_moe_custom_vjp_grads_match_autodiff():
    """The gather-only permutation custom_vjps (_pack_rows/_combine_rows
    route their transposes through the inverse slot map) must produce
    the same gradients as plain autodiff of the same indexing math —
    including through capacity drops, where the masks matter. Forward
    parity alone cannot catch a broken bwd rule."""
    import jax.numpy as jnp

    from horovod_tpu.parallel.moe import (
        _capacity,
        _route,
        moe_apply_dense,
    )

    rng = np.random.RandomState(9)
    tokens, d, k, cf = 16, 8, 2, 0.5  # tight capacity: real drops
    params = {
        "w": jnp.asarray(rng.randn(E, d, d) * 0.5, jnp.float32),
        "scale": jnp.asarray(1.0 + rng.rand(E, 1), jnp.float32),
    }
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    logits = jnp.asarray(rng.randn(tokens, E), jnp.float32)

    def autodiff_twin(params, x, logits):
        """Same routing + same indexing math, but with plain jnp ops so
        XLA autodiff derives every transpose (scatter-adds and all)."""
        capacity = _capacity(tokens, E, cf, k)
        probs = jax.nn.softmax(logits, axis=-1)
        routing, aux = _route(probs, capacity, k, True, x.dtype)
        buf = jnp.zeros((E * capacity, d), x.dtype)
        for e_idx, slot in zip(routing.expert_idx, routing.slot):
            flat = jnp.where(slot < capacity, e_idx * capacity + slot,
                             E * capacity)
            buf = buf.at[flat].add(x, mode="drop")
        out = jax.vmap(expert_fn)(params, buf.reshape(E, capacity, d))
        flat_out = out.reshape(E * capacity, d)
        y = None
        for e_idx, slot, w in zip(routing.expert_idx, routing.slot,
                                  routing.combine_w):
            safe = jnp.where(slot < capacity, e_idx * capacity + slot, 0)
            term = jnp.where((slot < capacity)[:, None],
                             flat_out[safe], 0) * w[:, None]
            y = term if y is None else y + term
        return y, aux

    def loss_fast(params, x, logits):
        y, aux = moe_apply_dense(expert_fn, params, x, logits,
                                 capacity_factor=cf, num_selected=k)
        return (y ** 2).sum() + 0.1 * aux

    def loss_twin(params, x, logits):
        y, aux = autodiff_twin(params, x, logits)
        return (y ** 2).sum() + 0.1 * aux

    gf = jax.jit(jax.grad(loss_fast, argnums=(0, 1, 2)))(params, x, logits)
    gt = jax.jit(jax.grad(loss_twin, argnums=(0, 1, 2)))(params, x, logits)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_moe_top2_default_capacity_no_drops_at_uniform_routing():
    """Capacity must provision k*T/E*factor slots: perfectly uniform top-2
    routing at the default capacity_factor=1.25 must drop nothing. (Under
    an unscaled T/E*factor capacity, ~37% of assignments would be dropped
    here.)"""
    params, x, _ = _setup(seed=3)
    # Token t's top-1 is expert t%E, top-2 is (t+1)%E: every expert receives
    # exactly 2T/E assignments.
    logits_np = np.full((E, T, E), -10.0, np.float32)
    for t in range(T):
        logits_np[:, t, t % E] = 10.0
        logits_np[:, t, (t + 1) % E] = 9.0
    logits = jnp.asarray(logits_np)
    y_default, _ = _run_moe(params, x, logits, k=2, capacity_factor=1.25)
    y_ample, _ = _run_moe(params, x, logits, k=2, capacity_factor=float(E))
    np.testing.assert_allclose(y_default, y_ample, rtol=1e-6, atol=1e-6)
    # And nothing passed through as zeros.
    assert (np.abs(y_default).sum(axis=-1) > 1e-9).all()


def test_moe_bf16_routing_matches_f32_many_tokens():
    """Slot arithmetic must stay exact in bf16: with >256 tokens routed to
    one expert a bf16 cumsum would collide slots and silently drop tokens."""
    rng = np.random.RandomState(5)
    T_big = 512
    params = {
        "w": jnp.asarray(rng.randn(E, D, D) * 0.5, jnp.float32),
        "scale": jnp.asarray(1.0 + rng.rand(E, 1), jnp.float32),
    }
    x = rng.randn(E, T_big, D).astype(np.float32)
    # Everything routed to expert 0; ample capacity -> zero drops expected.
    logits = np.zeros((E, T_big, E), np.float32)
    logits[:, :, 0] = 10.0
    mesh = make_mesh({"expert": E}, devices=jax.devices()[:E])

    def run(dtype):
        def body(p, xx, gg):
            y, _ = moe_apply(expert_fn, p, xx[0], gg[0],
                             axis_name="expert", capacity_factor=float(E))
            return y[None]

        f = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("expert"), P("expert"), P("expert")),
            out_specs=P("expert"), check_vma=False))
        return np.asarray(f(params, jnp.asarray(x, dtype),
                            jnp.asarray(logits, dtype)), np.float32)

    y16, y32 = run(jnp.bfloat16), run(jnp.float32)
    # No token may be zeroed (dropped) in bf16 when f32 keeps it.
    dropped16 = np.abs(y16).sum(axis=-1) < 1e-9
    dropped32 = np.abs(y32).sum(axis=-1) < 1e-9
    assert not dropped32.any()
    assert not dropped16.any(), f"{dropped16.sum()} tokens dropped in bf16"
    np.testing.assert_allclose(y16, y32, atol=0.05)


def test_moe_aux_loss_uniform_vs_skewed():
    params, x, logits = _setup(seed=3)
    _, aux_uniform = _run_moe(params, x, jnp.zeros_like(logits), k=1,
                              capacity_factor=float(E))
    skew = jnp.zeros_like(logits).at[:, :, 0].set(10.0)
    _, aux_skewed = _run_moe(params, x, skew, k=1, capacity_factor=float(E))
    # Uniform router probs with argmax collapse still >= 1; fully skewed
    # routing approaches E.
    assert aux_skewed[0] > aux_uniform[0]
    assert float(aux_skewed[0]) > E - 0.5


def test_moe_trains_end_to_end_dp_x_ep():
    """dp x ep: gradients flow through gates and experts; loss decreases."""
    import optax

    hvd.init()
    rng = np.random.RandomState(4)
    dp, ep = 2, 4
    mesh = make_mesh({"data": dp, "expert": ep})
    params = {
        "experts": {
            "w": jnp.asarray(rng.randn(ep, D, D) * 0.5, jnp.float32),
            "scale": jnp.asarray(1.0 + rng.rand(ep, 1), jnp.float32),
        },
        "gate": jnp.asarray(rng.randn(D, ep) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.randn(dp * T, D), jnp.float32)
    target = jnp.asarray(rng.randn(dp * T, D) * 0.1, jnp.float32)

    def body(p, xx, yy):
        logits = xx @ p["gate"]
        y, aux = moe_apply(expert_fn, p["experts"], xx, logits,
                           axis_name="expert", capacity_factor=2.0)
        loss = jnp.mean((xx + y - yy) ** 2) + 0.01 * aux
        return jax.lax.pmean(jax.lax.pmean(loss, "data"), "expert")

    tx = optax.adam(3e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(p, o, xx, yy):
        loss, g = jax.value_and_grad(lambda p_: jax.shard_map(
            body, mesh=mesh,
            in_specs=({"experts": P("expert"), "gate": P()},
                      P("data"), P("data")),
            out_specs=P(), check_vma=False)(p_, xx, yy))(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    losses = []
    for _ in range(200):
        params, opt_state, loss = step(params, opt_state, x, target)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::50]
    hvd.shutdown()
