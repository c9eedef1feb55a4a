"""One chip's share of a layer of routed experts (``ops/moe.HeldExperts``):
the routing against its equations, the bias at which the loads balance, the pair buffer's layout (every expert's rows on whole tiles),
the layer against a loop over experts forward and backward, padding rows
that give exactly zero, an overflow that is counted, and the shares of a
layer adding up to the uncut layer. Toy sizes; the grouped matmul is the chip's
kernel in interpret mode."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import moe

N, D, F, E, HELD, K, SCALE = 64, 16, 24, 16, 8, 4, 2.448


def _weights(seed=0, experts=E):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {
        "x": jax.random.normal(keys[0], (N, D)),
        "router": 0.5 * jax.random.normal(keys[1], (D, E)),
        "w_gate": 0.3 * jax.random.normal(keys[2], (experts, D, F)),
        "w_up": 0.3 * jax.random.normal(keys[3], (experts, D, F)),
        "w_down": 0.3 * jax.random.normal(keys[4], (experts, F, D)),
    }


def _experts(buffer):
    return moe.HeldExperts(routed=E, held=HELD, per_token=K, ff_dim=F,
                           pair_buffer=buffer, route_scale=SCALE)


def _route(w, bias=None):
    return moe.route(
        moe.router_scores(w["x"], w["router"]),
        jnp.zeros(E) if bias is None else bias, K, SCALE,
    )


def _share(w, first, buffer, bias=None):
    """The routed part of the share that holds experts ``first ..
    first + HELD - 1``: the layer holds the experts numbered from 0, so
    the router's columns and the experts are rolled to put them there."""
    bias = jnp.zeros((E,)) if bias is None else bias
    chosen, weights = moe.route(
        moe.router_scores(w["x"], jnp.roll(w["router"], -first, axis=1)),
        jnp.roll(bias, -first), K, SCALE,
    )
    held = slice(first, first + HELD)
    return moe.held_experts_ffn(
        w["x"], chosen, weights, w["w_gate"][held], w["w_up"][held],
        w["w_down"][held], _experts(buffer),
    )


def _loop(w, experts, bias=None):
    """The equations, expert by expert, dropless: of each token's chosen
    experts those in ``experts``, weighted over all the chosen."""
    x = w["x"]
    s = jax.nn.sigmoid(x @ w["router"])
    _, chosen = jax.lax.top_k(s if bias is None else s + bias, K)
    total = jnp.take_along_axis(s, chosen, -1).sum(-1) + 1e-20
    y = jnp.zeros_like(x)
    for e in experts:
        out = (jax.nn.silu(x @ w["w_gate"][e]) * (x @ w["w_up"][e])) @ (
            w["w_down"][e]
        )
        weight = jnp.where((chosen == e).any(-1), SCALE * s[:, e] / total, 0)
        y = y + weight[:, None] * out
    return y


class TestRouting:
    def test_weights_are_normalised_over_all_the_chosen(self):
        w = _weights()
        chosen, weights = _route(w)
        s = np.asarray(jax.nn.sigmoid(w["x"] @ w["router"]))
        for n in range(N):
            top = np.argsort(-s[n])[:K]
            assert set(np.asarray(chosen[n])) == set(top)
            np.testing.assert_allclose(
                np.sort(weights[n]),
                np.sort(SCALE * s[n, top] / (s[n, top].sum() + 1e-20)),
                rtol=1e-6,
            )
        np.testing.assert_allclose(weights.sum(-1), SCALE, rtol=1e-6)

    def test_the_bias_moves_the_choice_and_not_the_weights(self):
        w = _weights()
        bias = jnp.zeros(E).at[3].set(10.0)     # everyone now chooses 3
        chosen, weights = _route(w, bias)
        assert (chosen == 3).any(-1).all()
        s = jax.nn.sigmoid(w["x"] @ w["router"])
        picked = jnp.take_along_axis(s, chosen, -1)
        np.testing.assert_allclose(
            weights, SCALE * picked / picked.sum(-1, keepdims=True),
            rtol=1e-6,
        )
        grad = jax.grad(lambda b: _route(w, b)[1].sum())(bias)
        assert not np.asarray(grad).any()

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_the_bias_is_where_the_loads_balance(self, seed):
        """Tokens that share most of their router input choose the same
        few experts at a bias of zero; ``balanced_bias`` of their scores
        spreads them, and the layer routes with it."""
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        n, e = 512, 32
        common = 2.0 * jax.random.normal(keys[0], (1, D))
        x = common + jax.random.normal(keys[1], (n, D))
        router = 0.3 * jax.random.normal(keys[2], (D, e))
        scores = moe.router_scores(x, router)
        worst = lambda bias: float(jnp.max(moe.expert_loads(
            moe.route(scores, bias, K, SCALE)[0], e))) / (n * K / e)
        bias = moe.balanced_bias(scores, K)
        assert worst(jnp.zeros(e)) > 4.0
        assert worst(bias) < 1.25
        # the layer takes it anew for the tokens of each call, and no
        # gradient passes through it
        layer = moe.HeldExpertsMLP(
            moe.HeldExperts(routed=e, held=HELD, per_token=K, ff_dim=F,
                            pair_buffer=1024), dtype=jnp.float32,
        )
        tree = nn.meta.unbox(layer.init(keys[2], x[None]))["params"]
        assert set(tree) == {"router", "w_gate", "w_up", "w_down"}
        for tokens in (x, x[::-1] + 0.5 * common):
            _, counters = layer.apply({"params": tree}, tokens[None])
            assert float(counters["moe.load_max_over_mean"]) < 1.25
        grad = jax.grad(lambda s: jnp.sum(moe.route(
            s, moe.balanced_bias(s, K), K, SCALE)[1] ** 2))(scores)
        plain = jax.grad(lambda s: jnp.sum(moe.route(
            s, bias, K, SCALE)[1] ** 2))(scores)
        np.testing.assert_array_equal(grad, plain)


class TestThePairBuffer:
    @pytest.mark.parametrize("buffer, tile", [(256, 16), (384, 8), (1024, 64)])
    def test_every_experts_pairs_lie_in_order_on_whole_tiles(self, buffer,
                                                            tile):
        w = _weights(1)
        chosen, weights = _route(w)
        token, weight, bounds, held_pairs, overflowed = moe.dispatch(
            chosen, weights, HELD, buffer, tile
        )
        counts = [(np.asarray(chosen) == e).sum() for e in range(HELD)]
        assert int(held_pairs) == sum(counts) and int(overflowed) == 0
        # every row has its expert: the bounds run from 0 to the buffer,
        # each expert's rows are whole tiles, just enough for its pairs
        assert (int(bounds[0]), int(bounds[-1])) == (0, buffer)
        assert not (np.asarray(bounds) % tile).any()
        np.testing.assert_array_equal(
            np.diff(bounds)[:-1], [-(-c // tile) * tile for c in counts[:-1]]
        )
        for e, count in enumerate(counts):
            row = int(bounds[e])
            tokens_of_e = np.flatnonzero((np.asarray(chosen) == e).any(-1))
            np.testing.assert_array_equal(token[row:row + count], tokens_of_e)
            slot = np.argmax(np.asarray(chosen)[tokens_of_e] == e, axis=-1)
            np.testing.assert_array_equal(
                weight[row:row + count],
                np.asarray(weights)[tokens_of_e, slot],
            )
            # the rest of its tiles: weight 0, the token of the row's number
            rest = np.arange(row + count, int(bounds[e + 1]))
            assert not np.asarray(weight)[rest].any()
            np.testing.assert_array_equal(np.asarray(token)[rest], rest % N)

    def test_fewer_pairs_than_rows_are_padded(self):
        chosen = jnp.asarray([[0, 9, 10, 11], [1, 0, 12, 13]])
        weights = jnp.ones((2, 4))
        token, weight, bounds, held_pairs, overflowed = moe.dispatch(
            chosen, weights, 2, 16, 4
        )
        assert (int(held_pairs), int(overflowed)) == (3, 0)
        np.testing.assert_array_equal(bounds, [0, 4, 16])
        np.testing.assert_array_equal(token[:5], [0, 1, 0, 1, 1])
        np.testing.assert_array_equal(
            weight, [1, 1, 0, 0, 1] + [0] * 11
        )

    def test_the_tile_is_the_kernels_cut_to_the_buffer(self):
        tile = moe.HeldExperts(256, 8, 4, 3072).row_tile
        assert (tile(8192), tile(16384), tile(12288)) == (512, 512, 512)
        assert (tile(1024), tile(384), tile(64)) == (64, 24, 4)


class TestTheLayer:
    @pytest.mark.parametrize("buffer", [320, 512])
    def test_against_a_loop_over_experts_forward_and_backward(self, buffer):
        w = _weights(2)

        def layer(w):
            return _share(w, 0, buffer)[0]

        assert int(_share(w, 0, buffer)[2]) == 0
        got = layer(w)
        want = _loop(w, range(HELD))
        np.testing.assert_allclose(got, want, atol=5e-6)
        scalar = lambda f: lambda w: jnp.sum(jnp.sin(f(w)))
        grads = jax.grad(scalar(layer))(w)
        want_grads = jax.grad(scalar(lambda w: _loop(w, range(HELD))))(w)
        for name in w:
            np.testing.assert_allclose(
                grads[name], want_grads[name], atol=3e-5, err_msg=name
            )

    def test_padding_rows_give_exactly_zero(self):
        """The rows without a pair are computed like the rest (each with
        the token of its own number) and weigh nothing: a token none of
        whose choices is held gets exactly zero, forward and backward,
        and a buffer twice as large gives the same sums."""
        w = _weights(3)
        chosen, _ = _route(w)
        without = ~np.asarray((chosen < HELD).any(-1))
        assert without.any() and not without.all()
        tight, pairs, overflowed = _share(w, 0, 512)
        assert int(pairs) <= 512 and int(overflowed) == 0
        assert not np.asarray(tight)[without].any()
        assert np.asarray(tight)[~without].any(-1).all()
        grad = lambda b: jax.grad(
            lambda x: jnp.sum(jnp.sin(_share({**w, "x": x}, 0, b)[0])))(w["x"])
        # (through the router every token's scores move the weights of
        # none of its own pairs: exactly zero there too)
        assert not np.asarray(grad(512))[without].any()
        np.testing.assert_allclose(tight, _share(w, 0, 1024)[0], atol=5e-6)
        np.testing.assert_allclose(grad(512), grad(1024), atol=3e-5)

    def test_no_stated_size_never_overflows(self):
        """Twice a row for every pair: room for them all whatever their
        experts' last tiles leave empty, here with every token's four
        choices held."""
        w = _weights(6)
        bias = jnp.zeros(E).at[:K].set(10.0)
        chosen, weights = _route(w, bias)
        assert bool((chosen < K).all())
        experts = _experts(0)
        assert experts.buffer_rows(N) == 2 * N * K
        y, pairs, overflowed = moe.held_experts_ffn(
            w["x"], chosen, weights, w["w_gate"][:HELD], w["w_up"][:HELD],
            w["w_down"][:HELD], experts,
        )
        assert (int(pairs), int(overflowed)) == (N * K, 0)
        np.testing.assert_allclose(y, _loop(w, range(HELD), bias), atol=1e-5)

    @pytest.mark.parametrize("seed", [6, 7, 8])
    def test_the_kernel_visits_the_same_tiles_whatever_was_routed(self, seed):
        """The groups handed to the grouped matmul are whole tiles that
        add up to the buffer, whichever experts the seed made popular:
        the kernel's schedule has a tile for every row tile and none
        twice."""
        import importlib

        gmm_module = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm"
        )

        w = _weights(seed)
        chosen, weights = _route(w)
        tile = _experts(512).row_tile(512)
        bounds = moe.dispatch(chosen, weights, HELD, 512, tile)[2]
        sizes = jnp.diff(bounds)
        assert int(sizes.sum()) == 512 and not (np.asarray(sizes) % tile).any()
        (_, group_ids, tile_ids), tiles = gmm_module.make_group_metadata(
            group_sizes=sizes, m=512, tm=tile, start_group=0,
            num_nonzero_groups=HELD, visit_empty_groups=False,
        )
        assert int(tiles) == 512 // tile
        np.testing.assert_array_equal(
            np.asarray(tile_ids)[:int(tiles)], np.arange(512 // tile)
        )

    def test_an_overflow_is_counted_not_silent(self):
        w = _weights(4)
        chosen, weights = _route(w)
        held_pairs = int((np.asarray(chosen) < HELD).sum())
        _, pairs, overflowed = _share(w, 0, 64)
        assert int(pairs) == held_pairs > 64
        # whole tiles an expert: no fewer are lost than do not fit
        fitted = int(np.count_nonzero(moe.dispatch(
            chosen, weights, HELD, 64, _experts(64).row_tile(64))[1]))
        assert int(overflowed) == held_pairs - fitted >= held_pairs - 64
        counters = moe.routing_counters(chosen, E, pairs, 64, overflowed)
        assert set(counters) == set(moe.COUNTERS)
        assert counters["moe.pairs{kind=held}"] == held_pairs
        assert counters["moe.pairs{kind=buffer}"] == 64
        assert counters["moe.pairs{kind=overflowed}"] == held_pairs - fitted
        per_expert = np.bincount(np.asarray(chosen).ravel(), minlength=E)
        np.testing.assert_allclose(
            counters["moe.load_max_over_mean"],
            per_expert.max() / (N * K / E), rtol=1e-6,
        )
        assert int(_share(w, 0, 512)[2]) == 0

    def test_the_shares_add_up(self):
        """At 16 experts: the routed parts of the two shares of 8, plus
        the shared expert once, are the uncut layer."""
        w = _weights(5)
        both = _share(w, 0, 320)[0] + _share(w, HELD, 320)[0]
        np.testing.assert_allclose(both, _loop(w, range(E)), atol=5e-6)
        # every token's weights were normalised over all four chosen, so
        # the two partial sums carry the full route_scale between them
        shared = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (3, D, F))
        module = moe.HeldExpertsMLP(
            moe.HeldExperts(routed=E, held=HELD, per_token=K, ff_dim=F,
                            pair_buffer=320, route_scale=SCALE,
                            shared_ff_dim=F),
            dtype=jnp.float32,
        )

        def params(first):
            held = slice(first, first + HELD)
            return {
                "router": jnp.roll(w["router"], -first, axis=1),
                "w_gate": w["w_gate"][held], "w_up": w["w_up"][held],
                "w_down": w["w_down"][held], "shared_gate": shared[0],
                "shared_up": shared[1], "shared_down": shared[2].T,
            }

        x = w["x"].reshape(1, N, D)
        a, counters = module.apply({"params": params(0)}, x)
        b, _ = module.apply({"params": params(HELD)}, x)
        once = (jax.nn.silu(w["x"] @ shared[0]) * (w["x"] @ shared[1])) @ (
            shared[2].T
        )
        # (the module routes with the bias at which these tokens balance;
        # both shares score the same tokens, so they take the same one)
        bias = moe.balanced_bias(moe.router_scores(w["x"], w["router"]), K)
        np.testing.assert_allclose(
            (a + b)[0] - once, _loop(w, range(E), bias) + once, atol=1e-5
        )
        assert counters["moe.pairs{kind=overflowed}"] == 0

    def test_the_layers_parameters_and_their_count(self):
        e = moe.HeldExperts(routed=E, held=HELD, per_token=K, ff_dim=F,
                            pair_buffer=256, shared_ff_dim=F)
        shapes = jax.eval_shape(
            lambda: moe.HeldExpertsMLP(e).init(
                jax.random.PRNGKey(0), jnp.zeros((1, N, D)))
        )["params"]
        leaves = jax.tree_util.tree_leaves(nn.meta.unbox(shapes))
        assert sum(np.prod(x.shape) for x in leaves) == e.param_count(D)
        assert e.expected_pairs(N) == N * K * HELD / E
        assert e.active_param_count(D) == D * (E + 3 * F + 3 * F * K * HELD / E)
        with pytest.raises(ValueError):
            moe.HeldExperts(routed=8, held=9, per_token=2, ff_dim=4)
        with pytest.raises(ValueError):
            moe.HeldExperts(routed=8, held=8, per_token=9, ff_dim=4)
        with pytest.raises(ValueError):
            moe.HeldExperts(routed=8, held=8, per_token=2, ff_dim=4,
                            pair_buffer=4)
