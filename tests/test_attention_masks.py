"""The attention mask as a description (``ops/attention.AttentionMask``):
the kernels against the dense oracle under a windowed mask with and
without summary rows, the plain masks by either spelling, the schedule of
non-empty blocks and of their live and cut sub-tiles, the chunk summaries
against a loop, and the ``eva`` mixer of ``models/llama.py``. Toy sizes,
interpret mode."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (
    Llama,
    LlamaConfig,
    loss_fn,
    multibyte_loss_fn,
)
from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import (
    AttentionMask,
    flash_attention,
    pick_blocks,
    reference_attention,
)
from dlrover_tpu.ops.eva import chunk_summaries, eva_attention, eva_mask
from dlrover_tpu.utils import tracing

W, C, H, D = 32, 8, 2, 16


def _qkv(seq, keys=None, seed=0, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = [(batch, seq, H, D)] + [(batch, keys or seq, H, D)] * 2
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def _by_definition(seq, window, chunk, summaries):
    """The mask straight from the layer's equations, pair by pair."""
    dense = np.zeros((seq, summaries + seq), dtype=bool)
    for i in range(seq):
        for j in range(seq):
            dense[i, summaries + j] = j // window == i // window and j <= i
        for c in range(seq // chunk if summaries else 0):
            dense[i, c] = c < (i // window) * window // chunk
    return dense


def _the_oracle_as_it_was(q, k, v, causal):
    """``reference_attention`` before the mask was a description."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (1.0 / np.sqrt(D))
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        tril = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), s_k - s_q)
        logits = jnp.where(tril, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _loss_and_grads(fn, *args):
    def scalar(*a):
        out = fn(*a)
        return jnp.sum(out * jnp.cos(out)), out

    (_, out), grads = jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True
    )(*args)
    return out, grads


class TestTheDescription:
    @pytest.mark.parametrize("seq, summaries", [
        (W, 0), (3 * W, 0), (3 * W, 3 * W // C), (3 * W, 16), (4 * W, 32),
    ])
    def test_dense_is_the_equations(self, seq, summaries):
        mask = AttentionMask(window=W, summaries=summaries,
                             chunk=C if summaries else 0)
        np.testing.assert_array_equal(
            np.asarray(mask.dense(seq, summaries + seq)),
            _by_definition(seq, W, C, summaries),
        )
        assert mask.pairs(seq, summaries + seq) == _by_definition(
            seq, W, C, summaries
        ).sum()

    def test_the_plain_masks_are_the_old_ones(self):
        causal = np.asarray(AttentionMask().dense(5, 7))
        np.testing.assert_array_equal(causal, np.tril(np.ones((5, 7)), 2))
        assert AttentionMask().pairs(8, 8) == 36
        full = AttentionMask(causal=False)
        assert np.asarray(full.dense(3, 4)).all() and full.pairs(3, 4) == 12

    def test_the_published_sizes_give_the_issues_pair_count(self):
        mask = eva_mask(32768, 2048, 16, block_k=1024)
        assert mask == AttentionMask(window=2048, summaries=2048, chunk=16)
        assert mask.pairs(32768, 34816) == 33_570_816 + 31_457_280
        assert AttentionMask().pairs(32768, 32768) == 536_887_296

    @pytest.mark.parametrize("kwargs", [
        {"causal": False, "window": 8}, {"summaries": 4},
        {"window": 8, "summaries": 4}, {"window": 12, "summaries": 4,
                                        "chunk": 8},
    ])
    def test_a_description_that_says_nothing_is_refused(self, kwargs):
        with pytest.raises(ValueError):
            AttentionMask(**kwargs)

    def test_whole_windows_or_a_message(self):
        with pytest.raises(ValueError, match="whole windows"):
            eva_mask(48, 32, 8)
        assert eva_mask(32, 32, 8) == AttentionMask(window=32)
        assert eva_mask(96, 32, 8).summaries == 12
        assert eva_mask(96, 32, 8, block_k=16).summaries == 16


class TestBlocks:
    def test_a_block_that_does_not_divide_the_window_is_refused(self):
        mask = AttentionMask(window=32, summaries=16, chunk=8)
        assert pick_blocks(mask, 96, 112, 16, 16) == (16, 16)
        # 96 is no multiple of 64: the block would be halved to 32 (fine),
        # but 48 divides 96 and not the window
        assert pick_blocks(mask, 96, 112, 64, 16) == (32, 16)
        with pytest.raises(ValueError, match="does not divide the attention"):
            pick_blocks(mask, 96, 112, 48, 16)
        # summary rows that are no whole blocks: the block is halved until
        # it divides the key rows, and then divides them too
        assert pick_blocks(AttentionMask(window=32, summaries=12, chunk=8),
                           96, 108, 16, 16) == (16, 4)
        with pytest.raises(ValueError, match="whole windows"):
            pick_blocks(mask, 80, 96, 16, 16)
        # a plain mask still halves a block until it divides
        assert pick_blocks(AttentionMask(), 96, 96, 64, 64) == (32, 32)

    @pytest.mark.parametrize("seq, block_q, block_k", [
        (96, 16, 16), (128, 32, 16), (128, 8, 32), (64, 32, 32),
    ])
    @pytest.mark.parametrize("kv_major", [False, True])
    def test_the_schedule_visits_exactly_the_non_empty_blocks(
        self, seq, block_q, block_k, kv_major
    ):
        mask = eva_mask(seq, W, C, block_k)
        keys = seq + mask.summaries
        dense = np.asarray(mask.dense(seq, keys))
        nq, nk = seq // block_q, keys // block_k
        want = dense.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))
        np.testing.assert_array_equal(
            mask.live_blocks(seq, keys, block_q, block_k), want
        )
        (qs, ks, flags), _ = attention._schedule(
            mask, seq, keys, block_q, block_k,
            attention.sub_tile(block_q, block_k), kv_major
        )
        computing = flags >> 2 != 0
        visited = np.zeros_like(want)
        visited[qs[computing], ks[computing]] = True
        np.testing.assert_array_equal(visited, want)
        assert computing.sum() == want.sum()        # each once
        # Rows of blocks come one after another, each opened and closed
        # once; a row with no block still has its one step, not computing.
        rows = ks if kv_major else qs
        assert (np.diff(rows) >= 0).all()
        assert set(rows) == set(range(nk if kv_major else nq))
        for r in set(rows):
            f = flags[rows == r]
            assert f[0] & 1 and f[-1] & 2
            assert not (f[1:] & 1).any() and not (f[:-1] & 2).any()
        empty = ~want.any(axis=0 if kv_major else 1)
        assert (~computing).sum() == empty.sum()

    def test_the_plain_causal_grid_skips_above_the_diagonal(self):
        live = AttentionMask().live_blocks(128, 128, 32, 32)
        np.testing.assert_array_equal(live, np.tril(np.ones((4, 4), bool)))
        assert AttentionMask(causal=False).live_blocks(64, 64, 32, 32).all()

    def test_the_causal_kernels_want_a_key_row_a_query(self):
        """The description aligns the causal mask at the end (as the
        oracle always did), the kernels count from the start: one rule
        only on a square, and the kernels refuse anything else."""
        with pytest.raises(ValueError, match="one key row a query"):
            pick_blocks(AttentionMask(), 64, 128, 32, 32)
        q, k, v = _qkv(32, 64)
        with pytest.raises(ValueError, match="one key row a query"):
            flash_attention(q, k, v, block_q=16, block_k=16)
        # the oracle takes it, and a mask that is not causal runs
        assert reference_attention(q, k, v).shape == q.shape
        np.testing.assert_allclose(
            flash_attention(q, k, v, causal=False, block_q=16, block_k=16),
            reference_attention(q, k, v, causal=False), atol=2e-6)


def _rows_by_definition(rows, seq, window, chunk, summaries):
    """``_by_definition`` for some query rows of a long sequence, as
    array arithmetic: ``[len(rows), summaries + seq]``."""
    i = np.asarray(rows)[:, None]
    col = np.arange(summaries + seq)[None, :]
    j = col - summaries
    local = (col >= summaries) & (j // window == i // window) & (j <= i)
    remote = (col < min(summaries, seq // chunk)) & (
        col < (i // window) * (window // chunk)
    )
    return local | remote


class TestTheScheduleAtTheCellsShapes:
    """``evabyte.train32k`` times the kernels at 1 x 32768 with blocks of
    1024 (16 windows, 2048 summary rows, none of them padding) and holds
    their gradients against the reference at 1 x 8192 (4 windows, 512
    summary rows padded to 1024): two schedules. Both, and the 16384 in
    between, block by block against the layer's equations."""

    W, C, BLOCK = 2048, 16, 1024

    @pytest.fixture(scope="class", params=[8192, 16384, 32768])
    def shape(self, request):
        """The sequence, its mask and key rows; from the equations, a row
        of query blocks at a time: which blocks hold a pair (``[nq, nk]``),
        those blocks themselves, and the pairs in all."""
        seq = request.param
        mask = eva_mask(seq, self.W, self.C, self.BLOCK)
        keys = seq + mask.summaries
        nq, nk = seq // self.BLOCK, keys // self.BLOCK
        want, blocks, pairs = np.zeros((nq, nk), dtype=bool), {}, 0
        for qi in range(nq):
            rows = _rows_by_definition(
                np.arange(qi * self.BLOCK, (qi + 1) * self.BLOCK),
                seq, self.W, self.C, mask.summaries,
            ).reshape(self.BLOCK, nk, self.BLOCK)
            want[qi] = rows.any(axis=(0, 2))
            pairs += int(rows.sum())
            for ki in np.flatnonzero(want[qi]):
                blocks[qi, ki] = rows[:, ki, :].copy()
        return seq, mask, keys, want, blocks, pairs

    def test_the_sizes_are_the_cells(self, shape):
        seq, mask, keys, want, blocks, pairs = shape
        assert mask.summaries == {8192: 1024, 16384: 1024, 32768: 2048}[seq]
        assert pick_blocks(mask, seq, keys, self.BLOCK, self.BLOCK) == (
            self.BLOCK, self.BLOCK)
        assert pairs == mask.pairs(seq, keys)
        if seq == 32768:
            assert pairs == 65_028_096
            assert want.sum() == 92 and want.size == 1088

    @pytest.mark.parametrize("kv_major", [False, True])
    def test_the_schedule_is_the_non_empty_blocks(self, shape, kv_major):
        seq, mask, keys, want, blocks, pairs = shape
        np.testing.assert_array_equal(
            mask.live_blocks(seq, keys, self.BLOCK, self.BLOCK), want
        )
        (qs, ks, flags), _ = attention._schedule(
            mask, seq, keys, self.BLOCK, self.BLOCK,
            attention.sub_tile(self.BLOCK, self.BLOCK), kv_major
        )
        computing = flags >> 2 != 0
        visited = np.zeros_like(want)
        visited[qs[computing], ks[computing]] = True
        np.testing.assert_array_equal(visited, want)
        assert computing.sum() == want.sum()
        # every row of blocks is opened once and closed once, in order
        rows = ks if kv_major else qs
        assert (np.diff(rows) >= 0).all()
        assert set(rows) == set(range(want.shape[1 if kv_major else 0]))
        firsts, lasts = flags & 1 != 0, flags & 2 != 0
        assert firsts[0] and lasts[-1]
        np.testing.assert_array_equal(firsts[1:], np.diff(rows) > 0)
        np.testing.assert_array_equal(lasts[:-1], np.diff(rows) > 0)

    def test_the_mask_inside_each_block_it_runs(self, shape):
        seq, mask, keys, want, blocks, pairs = shape
        in_block = jax.jit(
            lambda qi, ki: mask.piece(qi * self.BLOCK, ki * self.BLOCK,
                                      self.BLOCK, self.BLOCK)
        )
        assert len(blocks) == want.sum()
        for (qi, ki), block in blocks.items():
            np.testing.assert_array_equal(
                np.asarray(in_block(qi, ki)), block,
                err_msg=f"block ({qi}, {ki}) of {seq}",
            )


class TestKernelsAgainstTheOracle:
    @pytest.mark.parametrize("seq, with_summaries", [
        (W, False),             # one window: no remote part
        (3 * W, True),          # three windows, summaries padded 12 -> 16
        (3 * W, False),         # windows alone
    ])
    def test_forward_and_the_three_gradients(self, seq, with_summaries):
        mask = eva_mask(seq, W, C, 16) if with_summaries else (
            AttentionMask(window=W)
        )
        q, k, v = _qkv(seq, seq + mask.summaries)
        want, want_g = _loss_and_grads(
            lambda *a: reference_attention(*a, mask=mask), q, k, v
        )
        got, got_g = _loss_and_grads(
            lambda *a: flash_attention(*a, mask=mask, block_q=16, block_k=16),
            q, k, v,
        )
        np.testing.assert_allclose(got, want, atol=2e-6)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=2e-5)
        if mask.summaries:
            # padding rows and the last window's summaries are seen by
            # no query: their gradient is exactly zero
            seen = (3 - 1) * W // C
            for g in got_g[1:]:
                assert not np.asarray(g[:, seen:mask.summaries]).any()

    def test_a_mask_and_a_bool_together_are_refused(self):
        q, k, v = _qkv(W)
        for fn in (reference_attention, flash_attention,
                   attention.flash_attention_shard):
            with pytest.raises(ValueError, match="not both"):
                fn(q, k, v, causal=True, mask=AttentionMask(window=W))
            with pytest.raises(ValueError, match="not both"):
                fn(q, k, v, causal=False, mask=AttentionMask())

    def test_one_window_is_the_causal_mask(self):
        q, k, v = _qkv(W)
        np.testing.assert_array_equal(
            flash_attention(q, k, v, mask=AttentionMask(window=W),
                            block_q=16, block_k=16),
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_the_plain_masks_are_unchanged_to_the_bit(self, causal):
        """``causal`` alone and the description that says the same run the
        same kernel and the same oracle."""
        q, k, v = _qkv(128, seed=3, batch=2)
        mask = AttentionMask(causal=causal)
        old, old_g = _loss_and_grads(
            lambda *a: flash_attention(*a, causal=causal, block_q=32,
                                       block_k=64), q, k, v)
        new, new_g = _loss_and_grads(
            lambda *a: flash_attention(*a, mask=mask, block_q=32,
                                       block_k=64), q, k, v)
        np.testing.assert_array_equal(old, new)
        for a, b in zip(old_g, new_g):
            np.testing.assert_array_equal(a, b)
        oracle = reference_attention(q, k, v, mask=mask)
        np.testing.assert_array_equal(
            oracle, _the_oracle_as_it_was(q, k, v, causal)
        )
        np.testing.assert_allclose(new, oracle, atol=2e-6)
        text = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, causal=causal, block_q=32, block_k=64))(q, k, v))
        assert text == str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, mask=mask, block_q=32, block_k=64))(q, k, v))


class TestSummaries:
    def test_against_a_loop_over_chunks(self):
        _, k, v = _qkv(3 * W, seed=1)
        phi, mu = (0.5 * jax.random.normal(key, (H, D))
                   for key in jax.random.split(jax.random.PRNGKey(2)))
        k_bar, v_bar = chunk_summaries(k, v, phi, mu, C)
        assert k_bar.shape == v_bar.shape == (1, 3 * W // C, H, D)
        for c in range(3 * W // C):
            for h in range(H):
                kc, vc = k[0, c * C:(c + 1) * C, h], v[0, c * C:(c + 1) * C, h]
                a = jax.nn.softmax(kc @ phi[h])
                np.testing.assert_allclose(
                    k_bar[0, c, h], a @ kc + mu[h], atol=1e-5)
                np.testing.assert_allclose(v_bar[0, c, h], a @ vc, atol=1e-5)
        with pytest.raises(ValueError, match="whole chunks"):
            chunk_summaries(k[:, :20], v[:, :20], phi, mu, C)

    def test_the_weights_are_float32_whatever_the_inputs(self):
        _, k, v = _qkv(W, seed=4)
        phi, mu = jnp.ones((H, D)), jnp.zeros((H, D))
        low = chunk_summaries(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                              phi, mu, C)
        assert low[0].dtype == low[1].dtype == jnp.bfloat16
        high = chunk_summaries(
            k.astype(jnp.bfloat16).astype(jnp.float32),
            v.astype(jnp.bfloat16).astype(jnp.float32), phi, mu, C)
        # the same inputs: only the result's rounding differs
        np.testing.assert_allclose(low[0].astype(jnp.float32), high[0],
                                   rtol=1e-2, atol=1e-2)

    @pytest.mark.parametrize("seq", [W, 3 * W])
    def test_the_mixer_by_the_kernel_and_by_the_oracle(self, seq):
        q, k, v = _qkv(seq, seed=5)
        phi, mu = (0.3 * jax.random.normal(key, (H, D))
                   for key in jax.random.split(jax.random.PRNGKey(6)))

        def mixer(impl):
            return lambda *a: eva_attention(
                *a, window=W, chunk=C, impl=impl, block_q=16, block_k=16)

        want, want_g = _loss_and_grads(mixer("xla"), q, k, v, phi, mu)
        got, got_g = _loss_and_grads(mixer("pallas"), q, k, v, phi, mu)
        np.testing.assert_allclose(got, want, atol=2e-6)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=5e-5)
        # one window has no remote part: phi and mu take no gradient
        assert bool(np.asarray(want_g[3]).any()) == (seq > W)
        # window 0 sees no summary: its rows are plain causal attention
        np.testing.assert_allclose(
            want[:, :W], reference_attention(q[:, :W], k[:, :W], v[:, :W]),
            atol=2e-6)


class TestTheCounter:
    def test_a_call_raises_allowed_and_computed_pairs(self, monkeypatch):
        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        mask = eva_mask(3 * W, W, C, 16)
        q, k, v = _qkv(3 * W, 3 * W + 16)
        flash_attention(q, k, v, mask=mask, block_q=16, block_k=16)
        totals = [e for e in fresh.events if e["name"] == "attn.pairs"][-1]
        live = mask.live_blocks(96, 112, 16, 16).sum()
        assert totals["ph"] == "C"
        # blocks of 16 are one sub-tile each: what is computed is the blocks
        assert totals["args"] == {
            "kind=allowed,seq=96": H * mask.pairs(96, 112),
            "kind=computed,seq=96": H * live * 16 * 16,
        }
        assert "attn.pairs" in tracing.SPANS


def _tiny(**more):
    base = dict(vocab_size=40, max_seq_len=128, num_layers=2, num_heads=4,
                d_model=32, d_ff=64, dtype=jnp.float32, attn_block_q=16,
                attn_block_k=16)
    return LlamaConfig(**{**base, **more})


EVA = dict(mixer="eva", attn_window=W, attn_chunk=C, norm_unit_offset=True,
           fp32_residual=True, fp32_logits=True, pred_heads=3,
           init_std=0.05)


class TestTheModel:
    def test_the_defaults_are_the_program_that_was(self):
        cfg = _tiny()
        assert (cfg.mixer, cfg.pred_heads, cfg.norm_unit_offset,
                cfg.fp32_residual, cfg.fp32_logits, cfg.init_std) == (
            "full", 1, False, False, False, 0.02)
        tokens = jnp.zeros((1, 64), jnp.int32)
        params = nn.meta.unbox(
            Llama(cfg).init(jax.random.PRNGKey(0), tokens)["params"])
        assert sorted(params["layers"]) == [
            "attn_norm", "down_proj", "gate_proj", "k_proj", "mlp_norm",
            "o_proj", "q_proj", "up_proj", "v_proj"]
        assert float(params["final_norm"]["scale"][0]) == 1.0
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        assert n == cfg.param_count()
        # 12 L d S a token, as it always counted the full square
        assert cfg.flops_per_token() == 6 * n + 12 * 2 * 32 * 128

    def test_the_eva_fields_build_the_architecture(self):
        cfg = _tiny(**EVA)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 40)
        model = Llama(cfg)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), tokens)["params"])
        layers = params["layers"]
        assert layers["summary_phi"].shape == (2, 4, 8)
        assert layers["summary_mu"].shape == (2, 4, 8)
        assert not np.asarray(params["final_norm"]["scale"]).any()  # offset
        assert params["lm_head"]["kernel"].shape == (32, 3 * 40)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        assert n == cfg.param_count()
        logits = model.apply({"params": params}, tokens)
        assert logits.shape == (2, 96, 120) and logits.dtype == jnp.float32
        mask = eva_mask(128, W, C)
        assert cfg.attention_pairs() == mask.pairs(128, 128 + mask.summaries)
        assert cfg.flops_per_token() == 6 * n + (
            12 * 2 * 32 * cfg.attention_pairs() / 128)

    def test_kernel_and_oracle_give_the_same_loss_and_gradients(self):
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 40)
        results = []
        for impl in ("xla", "pallas"):
            cfg = _tiny(**EVA, attn_impl=impl, remat=True,
                        remat_policy="dots_lite")
            model = Llama(cfg)
            params = nn.meta.unbox(
                model.init(jax.random.PRNGKey(0), tokens)["params"])
            results.append(jax.value_and_grad(lambda p: multibyte_loss_fn(
                model.apply({"params": p}, tokens), tokens, 3))(params))
        (l0, g0), (l1, g1) = results
        assert abs(float(l0) - float(l1)) < 1e-5
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(a, b, atol=2e-5)
        assert np.asarray(g1["layers"]["summary_phi"]).any()

    def test_a_sequence_that_is_no_whole_window_is_refused(self):
        cfg = _tiny(**EVA)
        with pytest.raises(ValueError, match="whole windows"):
            Llama(cfg).init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 80), jnp.int32))

    @pytest.mark.parametrize("change", [
        {"mixer": "linear"}, {"attn_window": 0}, {"attn_chunk": 5},
        {"attn_impl": "ring"},
    ])
    def test_a_config_the_mixer_cannot_run_is_refused(self, change):
        with pytest.raises(ValueError):
            _tiny(**{**EVA, **change})

    def test_the_scopes_are_in_the_steps_lowered_text(self):
        cfg = _tiny(**EVA, attn_impl="pallas")
        tokens = jnp.zeros((1, 64), jnp.int32)
        model = Llama(cfg)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        text = jax.jit(jax.grad(lambda p: multibyte_loss_fn(
            model.apply({"params": p}, tokens), tokens, 3
        ))).lower(params).as_text(debug_info=True)
        assert "attn.summaries" in text and "attn.mix" in text


class TestTheLoss:
    def test_one_head_is_the_next_token_loss(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 7))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 7)
        np.testing.assert_allclose(
            multibyte_loss_fn(logits, tokens, 1), loss_fn(logits, tokens),
            rtol=1e-6)

    def test_head_m_predicts_the_token_m_further(self):
        b, s, m, v = 2, 10, 3, 5
        logits = jax.random.normal(jax.random.PRNGKey(0), (b, s, m * v))
        tokens = np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, v))
        logp = np.asarray(jax.nn.log_softmax(logits.reshape(b, s, m, v)))
        heads = []
        for head in range(m):
            nll = [-logp[i, t, head, tokens[i, t + 1 + head]]
                   for i in range(b) for t in range(s - 1 - head)]
            assert len(nll) == b * (s - 1 - head)
            heads.append(np.mean(nll))
        np.testing.assert_allclose(
            multibyte_loss_fn(logits, tokens, m), np.mean(heads), rtol=1e-6)


def test_the_mask_is_hashable_and_static():
    a = AttentionMask(window=32, summaries=16, chunk=8)
    assert a == dataclasses.replace(a) and hash(a) == hash(
        AttentionMask(window=32, summaries=16, chunk=8))
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.window = 8


# ------------------------------------------------------- the sliding kind

def _sliding_by_definition(seq, window):
    """``i - window < j <= i``, pair by pair."""
    return np.array([[i - window < j <= i for j in range(seq)]
                     for i in range(seq)])


class TestTheSlidingKind:
    """A window that moves with the query (``sliding=True``): the same
    description, schedule and kernels as the aligned windows."""

    @pytest.mark.parametrize("windows", [1, 2, 4])
    def test_dense_bounds_and_pairs_are_the_equations(self, windows):
        seq = windows * W
        mask = AttentionMask(window=W, sliding=True)
        want = _sliding_by_definition(seq, W)
        np.testing.assert_array_equal(np.asarray(mask.dense(seq, seq)), want)
        assert mask.pairs(seq, seq) == want.sum()
        assert mask.pairs(seq, seq) == W * (W + 1) // 2 + (seq - W) * W

    def test_the_published_sizes_give_the_issues_pair_count(self):
        mask = AttentionMask(window=4096, sliding=True)
        assert mask.pairs(16384, 16384) == 58_722_304
        assert AttentionMask().pairs(16384, 16384) == 134_225_920
        # 1 + 2 + 3 + 4 + 12 x 5 blocks of 1024 x 1024, of 136 causal
        assert mask.live_blocks(16384, 16384, 1024, 1024).sum() == 70

    @pytest.mark.parametrize("kwargs", [
        {"sliding": True}, {"sliding": True, "window": 8, "causal": False},
        {"sliding": True, "window": 16, "summaries": 4, "chunk": 8},
    ])
    def test_a_description_that_says_nothing_is_refused(self, kwargs):
        with pytest.raises(ValueError):
            AttentionMask(**kwargs)

    def test_one_window_is_the_causal_mask_and_blocks_need_not_divide(self):
        q, k, v = _qkv(W)
        np.testing.assert_array_equal(
            flash_attention(q, k, v, mask=AttentionMask(window=W,
                                                        sliding=True),
                            block_q=16, block_k=16),
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        )
        # 24 divides neither 32 nor 96 / 32 windows: sequences are no
        # whole windows and a block lies across a window's edge
        mask = AttentionMask(window=24, sliding=True)
        assert pick_blocks(mask, 80, 80, 16, 16) == (16, 16)
        with pytest.raises(ValueError, match="one key row a query"):
            pick_blocks(mask, 64, 80, 16, 16)

    @pytest.mark.parametrize("windows, block_q, block_k", [
        (1, 16, 16), (2, 16, 32), (4, 32, 16), (4, 8, 64), (3, 32, 32),
    ])
    @pytest.mark.parametrize("kv_major", [False, True])
    def test_the_schedule_visits_exactly_the_non_empty_blocks(
        self, windows, block_q, block_k, kv_major
    ):
        seq = windows * W
        mask = AttentionMask(window=W, sliding=True)
        nq, nk = seq // block_q, seq // block_k
        want = _sliding_by_definition(seq, W).reshape(
            nq, block_q, nk, block_k
        ).any(axis=(1, 3))
        np.testing.assert_array_equal(
            mask.live_blocks(seq, seq, block_q, block_k), want
        )
        (qs, ks, flags), _ = attention._schedule(
            mask, seq, seq, block_q, block_k,
            attention.sub_tile(block_q, block_k), kv_major
        )
        assert (flags >> 2 != 0).all()      # no row of blocks is empty
        visited = np.zeros_like(want)
        visited[qs, ks] = True
        np.testing.assert_array_equal(visited, want)
        assert len(qs) == want.sum()        # each once

    @pytest.mark.parametrize("windows, block_q, block_k", [
        (1, 16, 16), (2, 16, 32), (4, 32, 16),
    ])
    def test_the_kernels_match_the_dense_oracle(self, windows, block_q,
                                                block_k):
        seq = windows * W
        mask = AttentionMask(window=W, sliding=True)
        q, k, v = _qkv(seq, seed=windows, batch=2)
        out, grads = _loss_and_grads(
            lambda *a: flash_attention(*a, mask=mask, block_q=block_q,
                                       block_k=block_k), q, k, v)
        want, want_grads = _loss_and_grads(
            lambda *a: reference_attention(*a, mask=mask), q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-6)
        for got, ref in zip(grads, want_grads):
            np.testing.assert_allclose(got, ref, atol=2e-5)
        # and the oracle is the equations: softmax over the allowed keys
        dense = _sliding_by_definition(seq, W)
        logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        logits = np.where(dense, logits, -np.inf)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            want, np.einsum("bhqk,bkhd->bqhd", p, v), atol=2e-6
        )

    def test_the_counter_counts_a_sliding_call(self, monkeypatch):
        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        q, k, v = _qkv(4 * W)
        mask = AttentionMask(window=W, sliding=True)
        flash_attention(q, k, v, mask=mask, block_q=16, block_k=16)
        totals = [e["args"] for e in fresh.events
                  if e["name"] == "attn.pairs"][-1]
        seq = 4 * W
        assert totals[f"kind=allowed,seq={seq}"] == H * mask.pairs(seq, seq)
        live = mask.live_blocks(seq, seq, 16, 16).sum()
        assert totals[f"kind=computed,seq={seq}"] == H * live * 16 * 16


# ------------------------------------------------------------ sub-tiles

def _allowed_rows(mask, rows, s_q, s_k):
    """``[len(rows), s_k]``: what these query rows see, from each kind's
    equations and not from ``bounds``."""
    i, col = np.asarray(rows)[:, None], np.arange(s_k)[None, :]
    if not mask.causal:
        return np.ones((len(rows), s_k), dtype=bool)
    if not mask.window:
        return col <= i
    if mask.sliding:
        return (col <= i) & (col > i - mask.window)
    return _rows_by_definition(rows, s_q, mask.window, mask.chunk or 1,
                               mask.summaries)


def _decode(mask, s_q, s_k, block_q, block_k, kv_major):
    """The schedule read back: the sub-tile's side, and ``[s_q / t, s_k /
    t]`` booleans of the sub-tiles it has the kernels compute and of
    those it has them compute under the mask. Held on the way: a strip's
    run is under the mask exactly where an edge crosses a sub-tile of
    it."""
    t = attention.sub_tile(block_q, block_k)
    (qs, ks, flags), classes = attention._schedule(
        mask, s_q, s_k, block_q, block_k, t, kv_major
    )
    nr, nc = block_q // t, block_k // t
    n_strips, n_along = (nc, nr) if kv_major else (nr, nc)
    whole = mask.tiles(s_q, s_k, t, t)[1]
    assert classes
    assert len(set(classes)) == len(classes)
    assert set(flags >> 2) - {0} == set(range(1, len(classes) + 1))
    live = np.zeros((s_q // t, s_k // t), dtype=bool)
    masked = np.zeros_like(live)
    for qi, ki, flag in zip(qs, ks, flags):
        if not flag >> 2:
            continue                            # no compute: nothing live
        kind = classes[(flag >> 2) - 1]
        assert len(kind) == n_strips
        for s, (a, b, cut) in enumerate(kind):
            assert 0 <= a <= b <= n_along
            run = [((i, s) if kv_major else (s, i)) for i in range(a, b)]
            run = [(qi * nr + r, ki * nc + c) for r, c in run]
            assert cut == any(not whole[at] for at in run)
            for at in run:
                assert not live[at]             # each sub-tile once
                live[at], masked[at] = True, cut
    assert not (live & ~whole & ~masked).any()
    return t, live, masked


_TOY_MASKS = {
    "causal": (AttentionMask(), 128),
    "full": (AttentionMask(causal=False), 64),
    "windows+summaries": (eva_mask(128, W, C, 32), 128),
    "windows": (AttentionMask(window=W), 96),
    "sliding": (AttentionMask(window=W, sliding=True), 128),
    "sliding, window no multiple": (AttentionMask(window=24, sliding=True),
                                    96),
}
# block_q, block_k, the module's sub-tile: blocks that are several
# sub-tiles, one, and smaller than the constant (the sub-tile is then
# their largest common divisor)
_TOY_BLOCKS = [(32, 32, 8), (32, 32, 16), (32, 32, 32), (32, 16, 16),
               (16, 32, 8), (16, 16, 256)]


class TestTheSubTiles:
    """The schedule's second level: of each block it lists, the sub-tiles
    that are live and the strips of them the mask's edge cuts, from the
    same ``bounds`` as everything else; the kernels compute the live ones
    and mask the cut strips only."""

    @pytest.mark.parametrize("blocks", [(1024, 1024), (256, 512), (48, 16),
                                        (8, 64)])
    def test_the_sub_tile_divides_the_block(self, blocks):
        t = attention.sub_tile(*blocks)
        assert blocks[0] % t == 0 and blocks[1] % t == 0
        assert t == {1024: 256, 256: 256, 48: 16, 8: 8}[blocks[0]]
        assert attention._SUB_TILE == 256

    @pytest.mark.parametrize("kind", list(_TOY_MASKS))
    @pytest.mark.parametrize("block_q, block_k, tile", _TOY_BLOCKS)
    @pytest.mark.parametrize("kv_major", [False, True])
    def test_dead_holds_no_pair_and_whole_no_masked_one(
        self, monkeypatch, kind, block_q, block_k, tile, kv_major
    ):
        monkeypatch.setattr(attention, "_SUB_TILE", tile)
        mask, seq = _TOY_MASKS[kind]
        keys = seq + mask.summaries
        t, live, masked = _decode(mask, seq, keys, block_q, block_k,
                                  kv_major)
        assert t == min(tile, block_q, block_k)
        dense = np.asarray(mask.dense(seq, keys))
        np.testing.assert_array_equal(
            dense, _allowed_rows(mask, np.arange(seq), seq, keys))
        by_tile = dense.reshape(seq // t, t, keys // t, t)
        # a dead sub-tile holds no allowed pair, one run bare no masked one
        np.testing.assert_array_equal(live, by_tile.any(axis=(1, 3)))
        assert by_tile.all(axis=(1, 3))[live & ~masked].all()
        # the description's two halves are the dense mask's, tile by tile
        in_tiles, whole = mask.tiles(seq, keys, t, t)
        np.testing.assert_array_equal(live, in_tiles)
        np.testing.assert_array_equal(whole, by_tile.all(axis=(1, 3)))
        # and the counter's computed pairs are the area of what runs
        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        attention.count_pairs(mask, 1, seq, keys, block_q, block_k)
        pairs = [e["args"] for e in fresh.events
                 if e["name"] == "attn.pairs"][-1]
        assert pairs[f"kind=computed,seq={seq}"] == live.sum() * t * t
        assert pairs[f"kind=allowed,seq={seq}"] == dense.sum()
        # and the first level is the second folded: a block is walked
        # where a sub-tile of it is live
        np.testing.assert_array_equal(
            live.reshape(seq // block_q, block_q // t,
                         keys // block_k, block_k // t).any(axis=(1, 3)),
            mask.live_blocks(seq, keys, block_q, block_k),
        )

    @pytest.mark.parametrize("kind", list(_TOY_MASKS))
    @pytest.mark.parametrize("block_q, block_k, tile", _TOY_BLOCKS[:5])
    def test_forward_and_the_three_gradients(self, monkeypatch, kind,
                                             block_q, block_k, tile):
        monkeypatch.setattr(attention, "_SUB_TILE", tile)
        mask, seq = _TOY_MASKS[kind]
        q, k, v = _qkv(seq, seq + mask.summaries, seed=7)
        want, want_g = _loss_and_grads(
            lambda *a: reference_attention(*a, mask=mask), q, k, v
        )
        got, got_g = _loss_and_grads(
            lambda *a: flash_attention(*a, mask=mask, block_q=block_q,
                                       block_k=block_k), q, k, v,
        )
        np.testing.assert_allclose(got, want, atol=2e-6)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=2e-5)

    def test_the_counter_counts_the_sub_tiles_that_run(self, monkeypatch):
        monkeypatch.setattr(attention, "_SUB_TILE", 8)
        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        attention.count_pairs(AttentionMask(), 3, 128, 128, 32, 32)
        pairs = [e["args"] for e in fresh.events
                 if e["name"] == "attn.pairs"][-1]
        # 4 diagonal blocks of 4 x 4 sub-tiles, 10 on or under the
        # diagonal; 6 blocks under it, whole
        assert pairs["kind=computed,seq=128"] == 3 * (4 * 10 + 6 * 16) * 64
        assert pairs["kind=allowed,seq=128"] == 3 * 128 * 129 // 2
        _, live, _ = _decode(AttentionMask(), 128, 128, 32, 32, False)
        assert pairs["kind=computed,seq=128"] == 3 * live.sum() * 64

    def test_a_description_whose_strips_are_no_run_is_refused(self):
        with pytest.raises(ValueError, match="no single run"):
            attention._run_of(np.array([True, False, True]))
        assert attention._run_of(np.zeros(4, dtype=bool)) == (0, 0)
        assert attention._run_of(np.array([0, 1, 1, 0])) == (1, 3)


class TestTheSubTilesAtTheCellsShapes:
    """Blocks of 1024 and the module's sub-tile, at the shapes the cells
    run: gpt2-xl's one block a head, mistral's 16384 causal, Trinity's
    sliding layers, EvaByte's windows and summaries at 32768. Sub-row by
    sub-row against each kind's equations, and the counters' readings that
    docs/attention_masks.md's table states."""

    BLOCK = 1024
    SHAPES = {
        # mask, queries, key rows; then a head's whole and cut blocks and
        # its computed pairs in blocks' worth
        "gpt2-xl": (AttentionMask(), 1024, 1024, 0, 1, 0.625),
        "mistral": (AttentionMask(), 16384, 16384, 120, 16, 130.0),
        "trinity sliding": (AttentionMask(window=4096, sliding=True),
                            16384, 16384, 42, 28, 59.5),
        "evabyte": (AttentionMask(window=2048, summaries=2048, chunk=16),
                    32768, 34816, 32, 60, 68.0),
    }

    @pytest.mark.parametrize("cell", list(SHAPES))
    @pytest.mark.parametrize("kv_major", [False, True])
    def test_the_description_pair_by_pair(self, cell, kv_major):
        mask, seq, keys = self.SHAPES[cell][:3]
        t, live, masked = _decode(mask, seq, keys, self.BLOCK, self.BLOCK,
                                  kv_major)
        assert t == attention._SUB_TILE
        for r in range(seq // t):
            rows = _allowed_rows(
                mask, np.arange(r * t, (r + 1) * t), seq, keys
            ).reshape(t, keys // t, t)
            np.testing.assert_array_equal(
                live[r], rows.any(axis=(0, 2)), err_msg=f"sub-row {r}")
            bare = live[r] & ~masked[r]
            assert rows.all(axis=(0, 2))[bare].all(), f"sub-row {r}"

    @pytest.mark.parametrize("cell", list(SHAPES))
    def test_the_schedule_and_the_counter_read_the_table(self, monkeypatch,
                                                         cell):
        mask, seq, keys, whole, cut, worth = self.SHAPES[cell]
        live, bare = mask.tiles(seq, keys, self.BLOCK, self.BLOCK)
        assert (bare.sum(), (live & ~bare).sum()) == (whole, cut)
        # the schedule walks them, and a whole block is the class whose
        # every strip runs all its sub-tiles bare
        (_, _, flags), classes = attention._schedule(
            mask, seq, keys, self.BLOCK, self.BLOCK,
            attention.sub_tile(self.BLOCK, self.BLOCK), False)
        n = self.BLOCK // attention._SUB_TILE
        all_of_it = ((0, n, False),) * n
        walked = [classes[(f >> 2) - 1] for f in flags if f >> 2]
        assert len(walked) == whole + cut
        assert sum(kind == all_of_it for kind in walked) == whole
        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        attention.count_pairs(mask, 2, seq, keys, self.BLOCK, self.BLOCK)
        pairs = [e["args"] for e in fresh.events
                 if e["name"] == "attn.pairs"][-1]
        assert pairs[f"kind=computed,seq={seq}"] == 2 * worth * 1024 ** 2
        assert pairs[f"kind=allowed,seq={seq}"] == 2 * mask.pairs(seq, keys)


class TestOneTraceACall:
    """What keeps the bodies' size out of the set-up time: the calls are
    jitted, so equal calls share a trace."""

    def test_the_layers_of_a_stack_share_their_traces(self, monkeypatch):
        traced = []
        call = attention._call
        monkeypatch.setattr(
            attention, "_call",
            lambda mask, kernel, *a: (traced.append(kernel.func.__name__),
                                      call(mask, kernel, *a))[1],
        )
        # a mask of this test's own: no trace of it is there yet
        mask = AttentionMask(window=24, sliding=True)
        q, k, v = _qkv(128, seed=3)

        @jax.checkpoint
        def layer(x):
            return flash_attention(x, k, v, mask=mask, block_q=32,
                                   block_k=32)

        # five layers, each run, recomputed and differentiated: the
        # forward call is traced once as it is run and once as it is
        # recomputed (two contexts to JAX), not once a layer
        jax.make_jaxpr(jax.grad(
            lambda x: layer(layer(layer(layer(layer(x))))).sum()
        ))(q)
        assert sorted(traced) == ["_bwd_dkv_kernel", "_bwd_dq_kernel",
                                  "_fwd_kernel", "_fwd_kernel"]

    def test_the_sub_tile_side_is_part_of_what_a_trace_is_kept_by(
        self, monkeypatch
    ):
        q, k, v = _qkv(64, seed=4)
        texts = []
        for tile in (8, 16):
            monkeypatch.setattr(attention, "_SUB_TILE", tile)
            texts.append(str(jax.make_jaxpr(lambda *a: flash_attention(
                *a, block_q=32, block_k=32))(q, k, v)))
        assert texts[0] != texts[1]
