"""Optimizer library tests: AGD, WeightedSAM, bf16 master weights,
8-bit Adam — math cross-checked against hand-rolled numpy references
and convergence on convex problems."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.optim import WeightedSAM, adam8bit, agd, bf16_master_weights


def quadratic_loss(target):
    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    return loss


def run_opt(opt, params, loss_fn, steps=100):
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        g = jax.grad(loss_fn)(params)
        updates, state = opt.update(g, state, params)
        return optax.apply_updates(params, updates), state

    for _ in range(steps):
        params, state = step(params, state)
    return params


class TestAGD:
    def test_matches_numpy_reference(self):
        """Three steps of AGD on a fixed gradient sequence, cross-checked
        against a step-by-step numpy transcription of the published
        algorithm (moment-difference preconditioner, clamped denom,
        bias-corrected lr)."""
        lr, b1, b2, delta = 0.1, 0.9, 0.999, 1e-5
        grads = [np.array([0.5, -1.0]), np.array([0.25, 0.5]),
                 np.array([-0.1, 0.2])]
        # numpy reference
        p = np.array([1.0, 2.0])
        m = np.zeros(2)
        v = np.zeros(2)
        for t, g in enumerate(grads, start=1):
            m_old = m.copy()
            m = b1 * m + (1 - b1) * g
            bc1, bc1_old = 1 - b1 ** t, 1 - b1 ** (t - 1)
            bc2 = 1 - b2 ** t
            d = m / bc1 if t == 1 else m / bc1 - m_old / bc1_old
            v = b2 * v + (1 - b2) * d * d
            den = np.maximum(np.sqrt(v), delta * np.sqrt(bc2))
            p = p - (lr * np.sqrt(bc2) / bc1) * (m / den)

        opt = agd(lr, b1=b1, b2=b2, delta=delta)
        params = {"w": jnp.array([1.0, 2.0])}
        state = opt.init(params)
        for g in grads:
            updates, state = opt.update({"w": jnp.asarray(g)}, state, params)
            params = optax.apply_updates(params, updates)
        np.testing.assert_allclose(np.asarray(params["w"]), p, rtol=1e-5)

    def test_converges_on_quadratic(self):
        target = jnp.array([3.0, -2.0, 0.5])
        params = run_opt(
            agd(0.1), {"w": jnp.zeros(3)}, quadratic_loss(target), 200
        )
        np.testing.assert_allclose(
            np.asarray(params["w"]), np.asarray(target), atol=1e-2
        )

    def test_decoupled_weight_decay_shrinks(self):
        opt = agd(0.1, weight_decay=0.1)
        params = {"w": jnp.ones(2)}
        state = opt.init(params)
        updates, _ = opt.update({"w": jnp.zeros(2)}, state, params)
        # Zero gradient: the only movement is the decay term -lr*wd*p.
        np.testing.assert_allclose(
            np.asarray(updates["w"]), -0.1 * 0.1 * np.ones(2), atol=1e-7
        )

    def test_clip_bounds_update(self):
        opt = agd(1.0, clip=0.001)
        params = {"w": jnp.zeros(2)}
        state = opt.init(params)
        updates, _ = opt.update({"w": jnp.array([100.0, -100.0])}, state,
                                params)
        assert np.all(np.abs(np.asarray(updates["w"])) <= 1.0 * 0.001 + 1e-9)


class TestWSAM:
    def test_rho_zero_equals_base(self):
        """With rho=0 the perturbation vanishes and decoupled WSAM's
        sharpness term is zero: it must reproduce the base optimizer."""
        target = jnp.array([1.0, -1.0])
        loss_fn = quadratic_loss(target)
        base = optax.sgd(0.1)
        wsam = WeightedSAM(optax.sgd(0.1), rho=0.0)
        p1 = {"w": jnp.zeros(2)}
        p2 = {"w": jnp.zeros(2)}
        s1, s2 = base.init(p1), wsam.init(p2)
        for _ in range(10):
            g = jax.grad(loss_fn)(p1)
            u, s1 = base.update(g, s1, p1)
            p1 = optax.apply_updates(p1, u)
            p2, s2, _ = wsam.step(loss_fn, p2, s2)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-6
        )

    @pytest.mark.parametrize("decouple", [True, False])
    def test_converges(self, decouple):
        target = jnp.array([2.0, 0.5])
        wsam = WeightedSAM(
            optax.adam(0.05), rho=0.05, decouple=decouple,
            sharpness_lr=0.05,
        )
        params = {"w": jnp.zeros(2)}
        state = wsam.init(params)
        loss_fn = quadratic_loss(target)

        @jax.jit
        def step(p, s):
            return wsam.step(loss_fn, p, s)

        for _ in range(300):
            params, state, loss = step(params, state)
        np.testing.assert_allclose(
            np.asarray(params["w"]), np.asarray(target), atol=5e-2
        )

    def test_perturbation_norm_is_rho(self):
        """e(w) has norm rho (non-adaptive): check via one manual step."""
        loss_fn = quadratic_loss(jnp.array([5.0, 5.0]))
        params = {"w": jnp.zeros(2)}
        g = jax.grad(loss_fn)(params)
        norm = float(optax.global_norm(g))
        wsam = WeightedSAM(optax.sgd(0.0), rho=0.1)
        scale = wsam.rho / (norm + wsam.sam_eps)
        e_w = float(optax.global_norm(
            jax.tree_util.tree_map(lambda x: x * scale, g)
        ))
        assert e_w == pytest.approx(0.1, rel=1e-4)


class TestBf16Master:
    def test_tiny_updates_accumulate(self):
        """Updates far below the bf16 ulp around 1.0 must still move the
        params over many steps — the whole point of fp32 masters."""
        opt = bf16_master_weights(optax.sgd(1e-4))
        params = {"w": jnp.ones(4, jnp.bfloat16)}
        state = opt.init(params)
        g = {"w": jnp.full(4, 0.01, jnp.bfloat16)}  # update = 1e-6/step

        @jax.jit
        def step(p, s):
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s

        for _ in range(5000):
            params, state = step(params, state)
        # 5000 * 1e-6 = 5e-3 total movement: invisible per-step in bf16
        # (ulp(1.0) ~ 7.8e-3) but accumulated by the master.
        w = np.asarray(params["w"], np.float32)
        assert np.all(w < 1.0), f"bf16 params never moved: {w}"
        master = np.asarray(state.master["w"])
        np.testing.assert_allclose(master, 1.0 - 5e-3, rtol=1e-3)

    def test_params_stay_bf16(self):
        opt = bf16_master_weights(optax.adam(1e-3))
        params = {"w": jnp.ones(4, jnp.bfloat16)}
        state = opt.init(params)
        u, state = opt.update(
            {"w": jnp.ones(4, jnp.bfloat16)}, state, params
        )
        new = optax.apply_updates(params, u)
        assert new["w"].dtype == jnp.bfloat16
        assert state.master["w"].dtype == jnp.float32


def _lies_transposed(shape) -> bool:
    """Whether a matrix pads less on the chip with its second-last axis
    along the 128 lanes (int8 tile 32 x 128): such a leaf's blocks run
    down its columns."""
    tile = lambda rows, width: -(-rows // 32) * 32 * -(-width // 128) * 128
    return len(shape) >= 2 and (
        tile(shape[-1], shape[-2]) < tile(shape[-2], shape[-1])
    )


def reference_adam8bit(g, p, m, v, step, lr, b1, b2, eps, wd, block=256):
    """One 8-bit Adam step in plain ``jax.numpy``, no kernel and no
    tiles: ``(new_p, update, (mq, mscale), (sq, sscale))``. A block is
    ``block`` consecutive elements of a row, the row's tail a shorter
    block; the scales lie ``[..., blocks, rows]``."""
    swap = lambda x: jnp.swapaxes(x, -1, -2)
    if _lies_transposed(g.shape):
        new_p, u, (mq, msc), (sq, ssc) = reference_adam8bit(
            swap(g), swap(p), (swap(m[0]), m[1]), (swap(v[0]), v[1]),
            step, lr, b1, b2, eps, wd, block,
        )
        return swap(new_p), swap(u), (swap(mq), msc), (swap(sq), ssc)
    width = g.shape[-1] if g.ndim else 1
    rows = g.shape[-2] if g.ndim >= 2 else 1
    blocks = -(-width // block)

    def blocked(x):
        x = jnp.asarray(x, jnp.float32).reshape(-1, rows, width)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, blocks * block - width)))
        return x.reshape(-1, rows, blocks, block)

    def flat(x):
        return x.reshape(-1, rows, blocks * block)[..., :width].reshape(
            g.shape
        )

    column = lambda scale: swap(scale.reshape(-1, blocks, rows))[..., None]
    lying = lambda scale: swap(scale[..., 0]).reshape(m[1].shape)
    bc1 = 1 - b1 ** jnp.float32(step)
    bc2 = 1 - b2 ** jnp.float32(step)
    lr_eff = -lr * jnp.sqrt(bc2) / bc1
    eps_eff = eps * jnp.sqrt(bc2)
    gb = blocked(g)
    m2 = blocked(m[0]) * (column(m[1]) * (b1 / 127.0)) + (1.0 - b1) * gb
    s_prev = blocked(v[0]) * (column(v[1]) / 127.0)
    s = jnp.sqrt(b2 * s_prev * s_prev + (1.0 - b2) * gb * gb)
    ssc = jnp.max(s, axis=-1, keepdims=True)
    sq = jnp.floor(s * jnp.where(ssc == 0, 1.0, 127.0 / ssc) + 0.5)
    u = lr_eff * m2 / (jnp.maximum(sq, 0.5) * (ssc / 127.0) + eps_eff)
    msc = jnp.max(jnp.abs(m2), axis=-1, keepdims=True)
    mq = jnp.round(m2 * jnp.where(msc == 0, 1.0, 127.0 / msc))
    new_p = blocked(p) * (1.0 - lr * wd) + u
    return (
        flat(new_p).astype(p.dtype), flat(u).astype(g.dtype),
        (flat(mq.astype(jnp.int8)), lying(msc)),
        (flat(sq.astype(jnp.int8)), lying(ssc)),
    )


HYPER = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, wd=0.1)


class TestAdam8bit:
    @pytest.mark.parametrize("shape, scale_shape", [
        ((300,), (2,)),                    # a vector is one row, with a tail
        ((64, 512), (2, 64)),
        ((3, 40, 320), (3, 2, 40)),        # scanned; the tail a block of 64
        ((2, 384, 200), (2, 2, 200)),      # lies transposed: blocks down columns
        ((), (1,)),
    ])
    def test_state_is_int8(self, shape, scale_shape):
        state = adam8bit(1e-3).init({"w": jnp.ones(shape)})
        for moment in (state.m["w"], state.v["w"]):
            assert moment.q.dtype == jnp.int8
            assert moment.q.shape == shape        # the parameter's own
            assert moment.scale.dtype == jnp.float32
            assert moment.scale.shape == scale_shape

    def test_state_bytes_a_parameter(self):
        """GPT-2 XL's leaves: the tails' extra scales and nothing padded
        keep the state within 1 % of 2 + 8 / 256 bytes a parameter."""
        leaves = [(48, 1600, 6400), (48, 6400, 1600), (48, 1600, 4800),
                  (48, 1600, 1600), (50257, 1600), (1024, 1600),
                  (48, 6400), (48, 4800), (48, 1600), (1600,)]
        params = {str(i): jax.ShapeDtypeStruct(s, jnp.bfloat16)
                  for i, s in enumerate(leaves)}
        state = jax.eval_shape(adam8bit(1e-3).init, params)
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves((state.m, state.v)))
        n = sum(int(np.prod(s)) for s in leaves)
        assert held / n < (2 + 8 / 256) * 1.01

    def test_a_block_is_whole_lane_tiles(self):
        with pytest.raises(ValueError, match="block_size 100"):
            adam8bit(1e-3, block_size=100)

    @pytest.mark.parametrize("shape, lies", [
        ((1600, 6400), False), ((6400, 1600), True), ((50257, 1600), True),
        ((1600, 1600), False), ((1600, 4800), False), ((48, 1600), False),
        ((4, 14336, 4096), False), ((4096, 32768), False),
    ])
    def test_rows_follow_the_chips_layout(self, shape, lies):
        from dlrover_tpu.optim.low_bit import _rows

        n_lead, rows, width, swapped = _rows(shape)
        assert swapped == lies == _lies_transposed(shape)
        assert (rows, width) == (shape[-2:][::-1] if lies else shape[-2:])
        assert n_lead * rows * width == int(np.prod(shape))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("shape", [
        (64, 512),       # whole blocks
        (40, 320),       # 256 + 64: the tail block
        (1100, 256),     # rows that do not divide the row tile
        (300,),          # a vector
        (3, 32, 320),    # scanned [L, A, B]
        (2, 384, 200),   # scanned, lying transposed
        (1290, 200),     # lying transposed, with a tail
    ])
    def test_kernel_equals_the_plain_reference(self, shape, dtype):
        """Two successive steps, fused and unfused, against
        ``reference_adam8bit``: int8 moments bit for bit, scales and
        parameters to float32 rounding."""
        rng = np.random.default_rng(len(shape) + shape[-1])
        opt = adam8bit(
            HYPER["lr"], HYPER["b1"], HYPER["b2"], HYPER["eps"], HYPER["wd"]
        )
        reference = jax.jit(
            lambda g, p, m, v, step: reference_adam8bit(
                g, p, m, v, step, **HYPER
            )
        )
        close = lambda a, b, atol=0.0, rtol=2e-6: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=rtol, atol=atol,
        )
        # A float32 result one rounding off may land on bfloat16's next step.
        ulp = 2e-6 if dtype == jnp.float32 else 2.0 ** -7
        p = jnp.asarray(rng.normal(size=shape), dtype)
        state = opt.init({"w": p})
        ref_p, ref_m, ref_v = p, tuple(state.m["w"]), tuple(state.v["w"])
        for step in (1, 2):
            spread = rng.uniform(0.01, 10.0, size=shape[-1:])
            g = jnp.asarray(rng.normal(size=shape) * spread, dtype)
            ref_p_old = ref_p
            ref_p, ref_u, ref_m, ref_v = reference(
                g, ref_p, ref_m, ref_v, step
            )
            u, unfused = opt.update({"w": g}, state, {"w": p})
            new_p, state = opt.update_and_apply({"w": g}, state, {"w": p})
            for got, want in ((state.m["w"], ref_m), (state.v["w"], ref_v)):
                np.testing.assert_array_equal(
                    np.asarray(got.q), np.asarray(want[0])
                )
                close(got.scale, want[1])
            close(new_p["w"], ref_p, atol=1e-6, rtol=ulp)  # values of order 1
            # Unfused: the same state bit for bit; the update carries the
            # decayed parameter outside the kernel.
            for a, b in zip(jax.tree_util.tree_leaves(unfused),
                            jax.tree_util.tree_leaves(state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            decay = (HYPER["lr"] * HYPER["wd"] * ref_p_old).astype(dtype)
            close(u["w"], ref_u - decay, atol=1e-8, rtol=ulp)
            p = new_p["w"]
        assert bool(jnp.all(jnp.isfinite(p.astype(jnp.float32))))

    def test_tracks_fp32_adam(self):
        """The quantized trajectory stays close to fp32 Adam on a
        well-conditioned quadratic."""
        target = jnp.array([1.5, -0.5, 2.0, 0.0])
        loss_fn = quadratic_loss(target)
        p_ref = run_opt(
            optax.adam(0.05), {"w": jnp.zeros(4)}, loss_fn, 150
        )
        p_q = run_opt(adam8bit(0.05), {"w": jnp.zeros(4)}, loss_fn, 150)
        np.testing.assert_allclose(
            np.asarray(p_q["w"]), np.asarray(p_ref["w"]), atol=0.05
        )

    def test_converges_large_param(self):
        rng = np.random.default_rng(0)
        target = jnp.asarray(rng.normal(size=(1024,)), jnp.float32)
        params = run_opt(
            adam8bit(0.05), {"w": jnp.zeros(1024)},
            quadratic_loss(target), 300,
        )
        err = np.max(np.abs(np.asarray(params["w"] - target)))
        assert err < 0.1, f"8-bit adam failed to converge: max err {err}"


class TestAccelIntegration:
    def test_agd_trains_gpt_sharded(self):
        """Custom optimizers are plain GradientTransformations: they must
        compose with auto_accelerate (state sharded like params)."""
        import dataclasses

        from dlrover_tpu.accel import ParallelSpec, auto_accelerate
        from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def token_loss(module, params, batch):
            return loss_fn(module.apply({"params": params}, batch), batch)

        res = auto_accelerate(
            model, agd(1e-3), tokens, token_loss,
            spec=ParallelSpec(data=2, fsdp=2),
        )
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(4):
            state, metrics = res.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]


class TestFusedApply:
    """adam8bit.update_and_apply must equal update + optax.apply_updates
    exactly (same kernel, apply folded into the output write)."""

    def test_fused_matches_unfused(self):
        import optax
        from dlrover_tpu.optim.low_bit import adam8bit

        params = {
            "stack": jnp.ones((4, 32, 96), jnp.float32) * 0.5,
            "w": jnp.ones((64, 160), jnp.float32) * 0.1,
        }
        grads = jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, 0.01), params
        )
        opt = adam8bit(1e-2, weight_decay=0.1)
        s0 = opt.init(params)
        u, s1 = opt.update(grads, s0, params)
        expect = optax.apply_updates(params, u)
        fused_p, s1f = opt.update_and_apply(grads, opt.init(params), params)
        for a, b in zip(
            jax.tree_util.tree_leaves(expect),
            jax.tree_util.tree_leaves(fused_p),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            )
        for a, b in zip(
            jax.tree_util.tree_leaves(s1), jax.tree_util.tree_leaves(s1f)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_train_step_uses_fused_path(self):
        """auto_accelerate's train step trains with the fused optimizer
        and matches the same model trained through plain update+apply
        (adamw), i.e. the hook does not change semantics."""
        import dataclasses
        import optax
        from dlrover_tpu.accel import ParallelSpec, auto_accelerate
        from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
        from dlrover_tpu.optim.low_bit import adam8bit

        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (4, 16), 0, cfg.vocab_size
        )
        res = auto_accelerate(
            GPT(cfg), adam8bit(1e-2), tokens,
            lambda mod, p, b: loss_fn(mod.apply({"params": p}, b), b),
            spec=ParallelSpec(),
        )
        state = res.state
        losses = []
        for _ in range(6):
            state, m = res.train_step(state, tokens)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert int(jax.device_get(state["step"])) == 6
