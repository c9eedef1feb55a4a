"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e (``benchmark/tools/record_trace.py gpt2-xl.steady``:
a 2-layer toy GPT, 4 steps traced, a 2 ms sleep in ``bench.next_batch``)
and on hand-made events."""

import os

import pytest

from benchmark import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ONE_CHIP = os.path.join(DATA, "toy_gpt2_one_chip.xplane.pb.gz")
# The same tool on four chips, ``gpt2-xl.fsdp4``: fsdp=4, AdamW, batch 16.
FOUR_CHIPS = os.path.join(DATA, "toy_gpt2_four_chips.xplane.pb.gz")

FWD = ('%blocks.33 = (bf16[100,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, '
       'f32[100,1,1024]{2,1,0:T(1,128)}) custom-call(bf16[100,1024,64]{2,1,0} '
       '%bitcast.355, bf16[100,1024,64]{2,1,0} %bitcast.357, '
       'bf16[100,1024,64]{2,1,0} %bitcast.359), '
       'custom_call_target="tpu_custom_call", '
       'frontend_attributes={kernel_metadata={}}')
DQ = ('%blocks.36 = bf16[100,1024,64]{2,1,0:T(8,128)(2,1)} custom-call('
      'bf16[100,1024,64]{2,1,0} %a, bf16[100,1024,64]{2,1,0} %b, '
      'f32[100,1,1024]{2,1,0} %pallas_call.53), '
      'custom_call_target="tpu_custom_call"')
DKV = ('%blocks.35 = (bf16[100,1024,64]{2,1,0}, bf16[100,1024,64]{2,1,0}) '
       'custom-call(bf16[100,1024,64]{2,1,0} %a, f32[100,1,1024]{2,1,0} %l), '
       'custom_call_target="tpu_custom_call"')
ADAM = ('%step.27 = (bf16[1920000,256]{1,0}, s8[1920000,256]{1,0}, '
        'f32[1920000,1]{1,0}, s8[1920000,256]{1,0}, f32[1920000,1]{1,0}) '
        'custom-call(f32[1,2]{1,0} %copy-done.89, bf16[1920000,256]{1,0} %g, '
        's8[1920000,256]{1,0} %m), custom_call_target="tpu_custom_call"')
MATMUL = ('%fusion.237 = (bf16[6400]{0}, bf16[4,1024,6400]{2,1,0}) fusion('
          'bf16[6400]{0} %x, bf16[48,4,1024,6400]{3,2,1,0} %y), '
          'kind=kOutput, calls=%fused_computation.278.clone.clone')
RESHAPE = ('%reshape.441 = bf16[48,6400,1600]{2,1,0:T(8,128)(2,1)} reshape('
           'bf16[1920000,256]{1,0:T(8,128)(2,1)} %pallas_call.145), '
           'sharding={replicated}')
WHILE = ('%while.6 = (s32[]{:T(128)}, bf16[4,256,256]{2,1,0}) while('
         '(s32[]{:T(128)}, bf16[4,256,256]{2,1,0}) %tuple.1), '
         'condition=%cond, body=%body')
GATHER = ('%all-gather-start.3 = (f32[400]{0}, f32[1600]{0}) '
          'all-gather-start(f32[400]{0} %p), dimensions={0}')
SCATTER = ('%fusion.9 = f32[400]{0} fusion(f32[1600]{0} %g), kind=kCustom, '
           'calls=%all-reduce-scatter.1')


class TestHloText:
    def test_an_instruction_is_taken_apart(self):
        op = xplane.parse_hlo(FWD)
        assert op["name"] == "blocks.33" and op["opcode"] == "custom-call"
        assert op["result"].startswith("(bf16[100,1024,64]")
        assert op["result"].endswith("f32[100,1,1024]{2,1,0:T(1,128)})")
        assert op["operands"].count("%bitcast") == 3
        assert "tpu_custom_call" in op["attributes"]
        assert xplane.parse_hlo("bench.next_batch")["opcode"] == ""

    @pytest.mark.parametrize("text, category, label", [
        (FWD, "mosaic", "flash_attention.fwd"),
        (DQ, "mosaic", "flash_attention.dq"),
        (DKV, "mosaic", "flash_attention.dkv"),
        (ADAM, "mosaic", "adam8bit"),
        (MATMUL, "matmul", "fusion.237 fusion (bf16[6400], bf16[4,1024,6400])"),
        # An operand that is a kernel's result does not make a kernel.
        (RESHAPE, "data_movement", "reshape.441 reshape bf16[48,6400,1600]"),
        (WHILE, "container", "while.6 while (s32[], bf16[4,256,256])"),
        (GATHER, "collective",
         "all-gather-start.3 all-gather-start (f32[400], f32[1600])"),
        (SCATTER, "collective", "fusion.9 fusion f32[400]"),
    ])
    def test_operations_are_told_apart(self, text, category, label):
        assert xplane.classify(text) == (category, label)


class TestIntervals:
    def test_self_time_is_duration_less_children(self):
        events = [
            (0.0, 100.0, "while"),      # spans the three below
            (10.0, 30.0, "a"),
            (30.0, 60.0, "call"),       # spans the next
            (35.0, 55.0, "b"),
            (100.0, 120.0, "c"),        # follows the while
        ]
        assert xplane.self_times(events) == [50.0, 20.0, 10.0, 20.0, 20.0]

    def test_union_merges_what_touches(self):
        assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
            [0, 3], [5, 8]
        ]

    def test_a_gap_goes_to_the_benchmarks_span_that_covers_it(self):
        spans = [(0.0, 100.0, "PjitFunction(step)"),
                 (40.0, 60.0, "bench.next_batch"),
                 (200.0, 300.0, "bench.on_step_end")]
        gaps = [(45.0, 55.0),     # inside both: the benchmark's span wins
                (10.0, 20.0),     # only the runtime's
                (150.0, 160.0)]   # nobody's
        assert xplane.attribute_gaps(gaps, spans) == {
            "bench.next_batch": 10.0, "PjitFunction(step)": 10.0,
            "host: no span": 10.0,
        }


class TestRecordedOneChipTrace:
    @pytest.fixture(scope="class")
    def reduced(self):
        return xplane.reduce(ONE_CHIP)

    def test_the_window_is_whole_steps_after_the_first(self, reduced):
        s, d = reduced["summary"], reduced["devices"][0]
        assert d["plane"] == "/device:TPU:0" and s["n_devices"] == 1
        assert s["module"].startswith("jit_step(")
        # Four steps were traced; the first began before the profiler.
        assert s["steps"] == 3
        assert s["step_span_s"] == pytest.approx([325e-6] * 3, rel=1e-3)
        first, last = d["steps"][0], d["steps"][-1]
        assert d["window_ns"] == [first[0], last[1]]
        assert s["window_s"] == pytest.approx(9.06283e-3, rel=1e-6)

    def test_busy_is_the_three_programs_and_idle_the_hosts_sleep(self, reduced):
        s = reduced["summary"]
        assert s["busy_s"] == pytest.approx(957.1e-6, rel=1e-4)
        assert s["busy_s"] == pytest.approx(sum(s["step_span_s"]), rel=0.02)
        # Self times add up to the busy time: nothing counted twice.
        assert sum(r["self_s"] for r in s["ops"].values()) == pytest.approx(
            s["busy_s"], rel=1e-6
        )
        assert sum(s["categories_s"].values()) == pytest.approx(
            s["busy_s"], rel=1e-6
        )
        # The toy's host slept 2 ms in next(batch) before every step.
        assert s["idle_gaps"][0][0] == "bench.next_batch"
        assert s["idle_gaps"][0][1] == pytest.approx(
            s["window_s"] - s["busy_s"], rel=1e-3
        )

    def test_kernels_are_found_and_counted(self, reduced):
        ops = reduced["summary"]["ops"]
        # 2 layers, 3 steps: forward twice a layer (`dots` remat runs it
        # again in the backward pass), dq and dkv once; 16 leaves updated.
        assert ops["flash_attention.fwd"]["count"] == 12
        assert ops["flash_attention.dq"]["count"] == 6
        assert ops["flash_attention.dkv"]["count"] == 6
        assert ops["adam8bit"]["count"] == 48
        assert all(ops[k]["category"] == "mosaic" for k in (
            "flash_attention.fwd", "flash_attention.dq",
            "flash_attention.dkv", "adam8bit",
        ))
        cats = reduced["summary"]["categories_s"]
        assert cats["mosaic"] == pytest.approx(435.881e-6, rel=1e-4)
        assert cats["matmul"] > 0 and cats["container"] < 1e-5
        assert "collective" not in cats

    def test_the_breakdown_is_the_ten_largest(self, reduced):
        s = reduced["summary"]
        assert len(s["device_ops"]) == 10
        times = [t for _, t in s["device_ops"]]
        assert times == sorted(times, reverse=True)
        assert s["device_ops"][0][0] == "flash_attention.fwd"

    def test_describe_lists_planes_and_lines(self):
        d = xplane.describe(ONE_CHIP, top=2)
        planes = {p["plane"]: p for p in d["planes"]}
        lines = {ln["line"]: ln for ln in planes["/device:TPU:0"]["lines"]}
        assert lines["XLA Modules"]["events"] == 4
        assert lines["XLA Ops"]["events"] > 1000
        assert "/host:CPU" in planes


class TestRecordedFourChipTrace:
    @pytest.fixture(scope="class")
    def reduced(self):
        return xplane.reduce(FOUR_CHIPS)

    def test_numbers_are_averaged_over_the_chips(self, reduced):
        s = reduced["summary"]
        assert [d["plane"] for d in reduced["devices"]] == [
            f"/device:TPU:{n}" for n in range(4)
        ]
        assert s["n_devices"] == 4 and s["steps"] == 3
        busy = [d["busy_ns"] / 1e9 for d in reduced["devices"]]
        assert max(busy) < 1.01 * min(busy)      # SPMD: the same work
        assert s["busy_s"] == pytest.approx(sum(busy) / 4)
        assert s["busy_s"] == pytest.approx(1.5343755e-3, rel=1e-6)
        assert sum(r["self_s"] for r in s["ops"].values()) == pytest.approx(
            s["busy_s"], rel=1e-6
        )
        assert s["idle_gaps"][0][0] == "bench.next_batch"

    def test_collectives_and_the_shard_mapped_kernel_are_found(self, reduced):
        s = reduced["summary"]
        # A chip's share of the batch goes through the same kernels as the
        # one-chip toy, as often; AdamW has no Pallas kernel.
        assert s["ops"]["flash_attention.fwd"]["count"] == 12
        assert s["ops"]["flash_attention.dq"]["count"] == 6
        assert "adam8bit" not in s["ops"]
        collectives = {
            k for k, r in s["ops"].items() if r["category"] == "collective"
        }
        assert any(k.startswith("all-gather") for k in collectives)
        assert any(k.startswith("all-reduce") for k in collectives)
        assert s["categories_s"]["collective"] == pytest.approx(
            463.451e-6, rel=1e-4
        )
        # Exposed: a chip's operations run one after another, so the
        # collectives' own time is time nothing else ran.
        assert s["categories_s"]["collective"] < s["busy_s"]


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.next_batch"):
        jnp.ones((8, 8)).block_until_ready()
    jax.profiler.stop_trace()
    assert xplane.reduce(str(tmp_path)) == {"devices": [], "summary": None}
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path / "nothing"))
