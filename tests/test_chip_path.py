"""Guards for what only the chip can break, checked without a chip.

The CPU mesh cannot catch a Mosaic kernel that GSPMD is asked to
partition: in interpret mode a Pallas kernel lowers to ordinary HLO. So
these tests compile ahead of time, interpret mode off, against libtpu's
description of a four-chip v5e host (no device needed; it says what
lowers, partitions and fits, nothing about runtime or speed).
"""

import json
import math
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dlrover_tpu import train as dtrain
from dlrover_tpu.ops import interpret as interpret_mode


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _all_reduces_by_loop(text: str) -> dict:
    """All-reduces inside the compiled module's ``while`` loops, by loop:
    ``forward`` for the layer scan of the forward pass, ``backward`` for
    its transpose (named so in the loop's ``op_name``)."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)

    def reached(root):
        seen, todo = set(), [root]
        while todo:
            comp = todo.pop()
            if comp in seen or comp not in bodies:
                continue
            seen.add(comp)
            for line in bodies[comp]:
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line
                )
        return seen

    out = {}
    for loop in re.finditer(r" while\(.*?body=%([\w.\-]+).*", text):
        kind = "backward" if "transpose(" in loop.group(0) else "forward"
        out[kind] = out.get(kind, 0) + sum(
            bool(re.search(r" all-reduce(?:-start)?\(", line))
            for comp in reached(loop.group(1)) for line in bodies[comp]
        )
    return out


@pytest.fixture(scope="module")
def v5e_2x2():
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Interpret mode off, as on the chip."""
    monkeypatch.setattr(interpret_mode, "use_interpret", lambda: False)


class TestAotOnV5eTopology:
    @pytest.mark.parametrize("shape, mask", [
        ((4, 1024, 25, 64), {}),                # gpt2-xl: one block a head
        ((1, 16384, 32, 128), {}),              # mistral: 136 of 256 blocks
        ((1, 16384, 48, 128), {"window": 4096, "sliding": True}),
        ((1, 32768, 32, 128), {"window": 2048, "summaries": 2048,
                               "chunk": 16}),   # EvaByte: nine classes
        ((1, 4096, 8, 128), {"causal": False}),  # every block, all whole
    ], ids=["gpt2-xl", "mistral-16k", "trinity-sliding-16k", "evabyte-32k",
            "not-causal"])
    def test_flash_attention_fwd_bwd_at_the_cells_shapes(
        self, v5e_2x2, compiled_kernels, shape, mask
    ):
        """The scheduled grid and the bodies of its classes of block, at
        the widths and blocks of the cells that run them (1024 x 1024):
        what Mosaic accepts of the sub-tiled pieces, their slices and the
        lane-kept statistics. Three calls: fwd, dq, dkv."""
        from dlrover_tpu.ops.attention import AttentionMask, flash_attention

        one = SingleDeviceSharding(v5e_2x2[0])
        mask = AttentionMask(**mask)
        b, s, h, d = shape
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
        kv = jax.ShapeDtypeStruct((b, s + mask.summaries, h, d),
                                  jnp.bfloat16, sharding=one)

        def loss(q, k, v):
            out = flash_attention(q, k, v, mask=mask, block_q=1024,
                                  block_k=1024)
            return jnp.sum(out.astype(jnp.float32))

        compiled = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2))
        ).lower(q, kv, kv).compile()
        assert _mosaic_calls(compiled) == 3  # fwd, dq, dkv

    def test_held_experts_over_the_pair_buffer_at_the_cells_widths(
        self, v5e_2x2, compiled_kernels
    ):
        """Sort, gather, the three grouped matmuls and their six backward
        products over the cell's 8192 rows at once, the scatter-add:
        nine Mosaic calls, shapes that do not follow what was routed."""
        from dlrover_tpu.ops import moe

        one = SingleDeviceSharding(v5e_2x2[0])
        shape = lambda *dims: jax.ShapeDtypeStruct(
            dims, jnp.bfloat16, sharding=one
        )

        def loss(x, router, w_gate, w_up, w_down):
            scores = moe.router_scores(x, router)
            chosen, weights = moe.route(
                scores, moe.balanced_bias(scores, 4), 4, 2.448
            )
            y, held, _ = moe.held_experts_ffn(
                x, chosen, weights, w_gate, w_up, w_down,
                moe.HeldExperts(routed=256, held=8, per_token=4,
                                ff_dim=3072, pair_buffer=8192),
            )
            return jnp.sum(y.astype(jnp.float32)), held

        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
        ).lower(
            shape(16384, 3072), shape(3072, 256), shape(8, 3072, 3072),
            shape(8, 3072, 3072), shape(8, 3072, 3072),
        ).compile()
        assert _mosaic_calls(compiled) == 9

    @staticmethod
    def _adam8bit_update_of(shape, device, donate=True):
        """``update_and_apply`` of one bfloat16 leaf, lowered for the chip
        with the state donated, as the train step donates it."""
        from dlrover_tpu.optim.low_bit import adam8bit

        one = SingleDeviceSharding(device)
        opt = adam8bit(2e-4)
        leaf = {"w": jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)}
        state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(opt.init, leaf),
        )
        return jax.jit(
            opt.update_and_apply, donate_argnums=(1, 2) if donate else ()
        ).lower(leaf, state, leaf)

    @pytest.mark.parametrize("shape", [
        (50257, 1600),      # the embedding: lies transposed, a tail of 81
        (2, 1600, 6400),    # scanned, rows of 25 whole blocks
        (2, 6400, 1600),    # scanned, lies transposed
        (2, 1600, 4800),    # scanned, a tail of 192
    ])
    def test_fused_adam8bit_moves_no_leaf(
        self, v5e_2x2, compiled_kernels, shape
    ):
        """One Mosaic call, every operand read and written where it lies:
        nothing of the leaf's size is copied, padded, transposed, sliced
        or re-laid around it, and nothing is held besides the arguments
        (what refused PR 25 and cost the parent a fifth of its step)."""
        compiled = self._adam8bit_update_of(shape, v5e_2x2[0]).compile()
        assert _mosaic_calls(compiled) == 1
        moved = []
        for dims, op in re.findall(
            r"= \(?\w+\[([\d,]+)\]\S* "
            r"(copy|copy-start|pad|transpose|slice|dynamic-slice|reshape)\(",
            compiled.as_text(),
        ):
            if math.prod(int(d) for d in dims.split(",")) >= math.prod(shape):
                moved.append((op, dims))
        assert not moved, moved
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < math.prod(shape) // 8

    def test_adam8bit_kernel_does_not_grow_with_the_leaf(
        self, v5e_2x2, compiled_kernels
    ):
        """The leaf's width is on the grid, not unrolled into the body:
        tracing and lowering a 32768-wide leaf is the work of a 256-wide
        one (a body that grew with the width cost long16k 3.3 s of
        ``setup.build_s`` in PR 25)."""
        narrow = self._adam8bit_update_of((64, 256), v5e_2x2[0]).as_text()
        wide = self._adam8bit_update_of((64, 32768), v5e_2x2[0]).as_text()
        assert "tpu_custom_call" in narrow
        assert len(wide) <= 1.2 * len(narrow), (len(wide), len(narrow))

    def test_pallas_gpt_step_partitions_over_data_and_fsdp(
        self, v5e_2x2, compiled_kernels
    ):
        """``attn_impl="pallas"`` under a mesh of more than one chip: dies
        at lowering ("Mosaic kernels cannot be automatically
        partitioned") unless the kernel is wrapped in shard_map."""
        from dlrover_tpu.accel import ParallelSpec
        from dlrover_tpu.accel.accelerate import make_train_step
        from dlrover_tpu.accel.mesh import create_mesh
        from dlrover_tpu.accel.sharding import state_shardings, unbox
        from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

        cfg = GPTConfig(
            vocab_size=512, max_seq_len=256, num_layers=2, num_heads=2,
            d_model=256, attn_impl="pallas", attn_block_q=128,
            attn_block_k=128, remat=True, remat_policy="dots",
        )
        spec = ParallelSpec(data=2, fsdp=2)
        mesh = create_mesh(spec.axes(), devices=v5e_2x2)
        rules = spec.rules(vocab_size=cfg.vocab_size)
        model, opt = GPT(cfg), optax.adamw(1e-3)
        tokens = jnp.zeros((8, 256), jnp.int32)

        def init_fn(rng):
            params = model.init(rng, tokens)["params"]
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        def token_loss(module, params, batch):
            return loss_fn(module.apply({"params": params}, batch), batch)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        shardings = state_shardings(mesh, abstract, rules)
        batch_sharding = NamedSharding(mesh, P(dict(rules)["batch"], None))
        step = make_train_step(
            model, opt, token_loss, mesh, rules, shardings, batch_sharding
        )
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            unbox(abstract), shardings,
        )
        batch = jax.ShapeDtypeStruct(
            tokens.shape, tokens.dtype, sharding=batch_sharding
        )
        compiled = step.lower(state, batch).compile()
        # fwd, dq, dkv in the scanned layer: under ``dots`` the block keeps
        # what the forward kernel wrote, so the chip's compiler is handed
        # no second forward (tests/test_attention_residuals.py).
        assert _mosaic_calls(compiled) == 3
        assert " all-gather(" in compiled.as_text()


    def test_fsdp_step_gathers_no_vector_inside_the_layer_loops(
        self, v5e_2x2, compiled_kernels
    ):
        """gpt2-xl's widths at two scanned layers under ``fsdp=4``: the
        step of the ``gpt2-xl.fsdp4`` cell but for its depth. A vector a
        layer sharded over fsdp is gathered by a synchronous all-reduce
        wherever a layer uses it (two a layer in the forward loop, two
        more for the rematerialized forward in the backward loop, 180-310
        µs each on the chip); kept whole it needs none, and the backward
        loop keeps only the two that reduce the vectors' gradients. The
        matrices' gathers and scatters are those of the step that sharded
        every vector."""
        from dlrover_tpu.accel import ParallelSpec
        from dlrover_tpu.accel.accelerate import make_train_step
        from dlrover_tpu.accel.mesh import create_mesh
        from dlrover_tpu.accel.sharding import state_shardings, unbox
        from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

        cfg = GPTConfig(
            vocab_size=50257, max_seq_len=1024, num_layers=2, num_heads=25,
            d_model=1600, param_dtype=jnp.float32, remat=True,
            remat_policy="dots", attn_impl="pallas", attn_block_q=1024,
            attn_block_k=1024,
        )
        spec = ParallelSpec(fsdp=4)
        mesh = create_mesh(spec.axes(), devices=v5e_2x2)
        rules = spec.rules(vocab_size=cfg.vocab_size)
        model, opt = GPT(cfg), optax.adamw(2e-4)
        tokens = jnp.zeros((16, 1024), jnp.int32)

        def init_fn(rng):
            params = model.init(rng, tokens)["params"]
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        def token_loss(module, params, batch):
            return loss_fn(module.apply({"params": params}, batch), batch)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        shardings = state_shardings(mesh, abstract, rules)
        batch_sharding = NamedSharding(mesh, P(dict(rules)["batch"], None))
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            unbox(abstract), shardings,
        )
        batch = jax.ShapeDtypeStruct(
            tokens.shape, tokens.dtype, sharding=batch_sharding
        )
        text = make_train_step(
            model, opt, token_loss, mesh, rules, shardings, batch_sharding
        ).lower(state, batch).compile().as_text()
        assert _all_reduces_by_loop(text) == {"forward": 0, "backward": 2}
        matrices = {
            "all-gather": len(re.findall(r" all-gather(?:-start)?\(", text)),
            "reduce-scatter": text.count("calls=%all-reduce-scatter"),
            "collective-permute": len(
                re.findall(r" collective-permute(?:-start)?\(", text)
            ),
        }
        assert matrices == {
            "all-gather": 12, "reduce-scatter": 1, "collective-permute": 49,
        }


class TestInterpretIsADecision:
    def test_only_a_named_cpu_platform_interprets(self, monkeypatch):
        assert interpret_mode.use_interpret()  # conftest names the CPU
        for platforms, want in (
            ("", False), (None, False), ("tpu", False), ("tpu,cpu", False),
            ("cpu", True), ("cpu,tpu", True),
        ):
            monkeypatch.setattr(interpret_mode, "jax", SimpleNamespace(
                config=SimpleNamespace(jax_platforms=platforms)
            ))
            assert interpret_mode.use_interpret() is want, platforms

    def test_adam8bit_refused_on_a_mesh_by_name(self):
        from dlrover_tpu.accel import ParallelSpec, auto_accelerate
        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.optim.low_bit import adam8bit

        tokens = jnp.zeros((2, 16), jnp.int32)
        with pytest.raises(ValueError, match="adam8bit"):
            auto_accelerate(
                GPT(GPTConfig.tiny()), adam8bit(1e-3), tokens,
                lambda m, p, b: 0.0, spec=ParallelSpec(data=2),
            )


class TestCompileCachePlacement:
    @pytest.fixture
    def cache_config(self):
        """Restore whatever the session's cache configuration was."""
        names = (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
        saved = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in saved.items():
            jax.config.update(n, v)

    def test_placed_from_outside_sets_no_directory(
        self, monkeypatch, tmp_path, cache_config
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "left-alone")
        assert dtrain.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "left-alone"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_unset_uses_the_one_checkout_path(
        self, monkeypatch, cache_config
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        # Nothing about the job, the process or the clock may move it.
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "some-other-job")
        assert dtrain.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert dtrain.CHECKOUT_COMPILE_CACHE == want


class TestOneChipPerWorker:
    def test_layouts_the_assignment_cannot_serve_are_refused(self):
        from dlrover_tpu.agent.tpu_chips import check_layout

        check_layout(nproc_per_node=4, max_nodes=1, chips=0)  # CPU host
        check_layout(nproc_per_node=1, max_nodes=8, chips=4)
        check_layout(nproc_per_node=4, max_nodes=1, chips=4)
        with pytest.raises(ValueError, match="nproc_per_node=2"):
            check_layout(nproc_per_node=2, max_nodes=1, chips=4)
        with pytest.raises(ValueError, match="nnodes"):
            check_layout(nproc_per_node=4, max_nodes=2, chips=4)

    def test_each_worker_gets_its_own_chip_and_the_same_address_list(self):
        from dlrover_tpu.agent.tpu_chips import worker_chip_env

        ports = [8476, 8477, 8478, 8479]
        envs = [worker_chip_env(rank, ports) for rank in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
        assert [e["TPU_PROCESS_PORT"] for e in envs] == [
            str(p) for p in ports
        ]
        assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
        assert envs[0]["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert envs[0]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


class TestChipSmokeContract:
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    SMOKE = os.path.join(REPO, "chip_smoke.py")

    def _run(self, *args, cwd=None, script=None, timeout=300, fsize=None):
        import resource

        from tests.conftest import cpu_subprocess_env

        def limit():  # hard and soft, as `ulimit -f` sets them
            resource.setrlimit(resource.RLIMIT_FSIZE, (fsize, fsize))

        return subprocess.run(
            [sys.executable, script or self.SMOKE, *args], cwd=cwd,
            env=cpu_subprocess_env(), capture_output=True, text=True,
            timeout=timeout, preexec_fn=limit if fsize else None,
        )

    def test_no_accelerator_is_a_failure_without_a_result(self):
        r = self._run()
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        assert r.stdout.strip() == ""

    def test_the_script_alone_fails(self, tmp_path):
        import shutil

        alone = shutil.copy(self.SMOKE, tmp_path)
        r = self._run(cwd=tmp_path, script=alone)
        assert r.returncode != 0
        assert r.stdout.strip() == ""

    @pytest.mark.slow
    def test_cpu_rehearsal_of_the_one_chip_leg(self):
        """The smoke's whole control flow — launcher, device check, fork
        server, kill, flush, restart, restore, cache hit — at a toy width,
        and under a file-size limit smaller than the toy state (574 kB):
        the driver's chip machine has one smaller than the real state, and
        the first smoke died there with EFBIG."""
        fsize = 384 << 10
        r = self._run("--cpu-rehearsal", "--legs", "one", fsize=fsize)
        assert r.returncode == 0, r.stderr[-3000:]
        lines = r.stdout.strip().splitlines()
        assert json.loads(lines[-1]) == {"rehearsal": "cpu", "passed": True}
        leg = json.loads(lines[0])
        assert leg["file_size_limit"] == fsize
        assert leg["resumed_at_step"] == leg["killed_at_step"]
        assert leg["restart"]["cache_misses"] == 0
