"""The cells' programs compiled ahead of time for a described v5e:2x2 (no
chip): what lowers, partitions and fits. Nothing here says anything about
time. The topology is described in a module-scoped fixture, never at
import (one process at a time may load libtpu), and every test of this
kind is in this one file."""

import copy

import pytest

from benchmark import cells
from benchmark.tools import aot

V5E_HBM_BYTES = 16_909_336_064     # bytes_limit the chip reports (PERF.md)


@pytest.fixture(scope="module")
def v5e_2x2():
    try:
        return aot.v5e_2x2()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Interpret mode off, as on the chip."""
    from dlrover_tpu.ops import interpret as interpret_mode

    monkeypatch.setattr(interpret_mode, "use_interpret", lambda: False)


def _cell(name, layers=None):
    cell = copy.deepcopy(cells.resolve(name, cells.ROOT))
    if layers:       # fewer layers compile in seconds; widths stay
        key = "n_layer" if cell["family"] == "gpt2" else "num_hidden_layers"
        cell["config"][key] = layers
    return cell


class TestTheMistralCut:
    def test_four_layers_at_16k_fit_one_chip_with_room(
        self, v5e_2x2, compiled_kernels
    ):
        compiled = aot.programs(_cell("mistral-7b.long16k"), v5e_2x2)["step"]()
        peak = compiled.memory_analysis().peak_memory_in_bytes
        assert peak < V5E_HBM_BYTES - 1_000_000_000, peak
        assert peak > 0.8 * V5E_HBM_BYTES      # and the chip is full
        counts = aot.hlo_counts(compiled.as_text())
        # flash attention: forward, forward again (remat), dq, dkv in the
        # scanned layer; one fused Adam call for each of the 12 leaves.
        assert counts["mosaic_calls"] == 16

    @pytest.mark.slow
    def test_a_fifth_layer_is_refused(self, v5e_2x2, compiled_kernels):
        todo = aot.programs(_cell("mistral-7b.long16k", layers=5), v5e_2x2)
        with pytest.raises(Exception, match="Ran out of memory"):
            todo["step"]()

    @pytest.mark.slow
    def test_the_reference_and_gradient_programs_fit_beside_the_state(
        self, v5e_2x2, compiled_kernels
    ):
        todo = aot.programs(_cell("mistral-7b.long16k"), v5e_2x2)
        moments = 2 * 1_140_887_552 * 1.02     # int8 m and v, their scales
        for what in ("ref", "ref_losses", "sys"):
            peak = todo[what]().memory_analysis().peak_memory_in_bytes
            assert peak + moments < V5E_HBM_BYTES - 1e9, (what, peak)


class TestTheGpt2Cells:
    def test_the_one_chip_step_holds_both_kernels(
        self, v5e_2x2, compiled_kernels
    ):
        compiled = aot.programs(
            _cell("gpt2-xl.steady", layers=1), v5e_2x2
        )["step"]()
        counts = aot.hlo_counts(compiled.as_text())
        # attention forward, dq, dkv (one layer: no scan, no second
        # forward) and one fused Adam call for each of the 16 leaves
        assert counts["mosaic_calls"] > 16
        assert counts["all_gather"] == counts["reduce_scatter"] == 0

    def test_the_elastic_cell_runs_the_controls_step(self):
        steady = cells.resolve("gpt2-xl.steady", cells.ROOT)
        elastic = cells.resolve("gpt2-xl.elastic", cells.ROOT)
        assert steady["config"] == elastic["config"]
        same = ("batch", "sequence", "param_dtype", "optimizer", "parallel",
                "remat", "attention", "data", "warmup_steps")
        assert all(steady["job"][k] == elastic["job"][k] for k in same)

    def test_the_four_chip_step_gathers_scatters_and_keeps_its_kernel(
        self, v5e_2x2, compiled_kernels
    ):
        compiled = aot.programs(
            _cell("gpt2-xl.fsdp4", layers=1), v5e_2x2
        )["step"]()
        counts = aot.hlo_counts(compiled.as_text())
        assert counts["all_gather"] > 0 and counts["reduce_scatter"] > 0
        assert 3 <= counts["mosaic_calls"] <= 4    # attention, shard_mapped
        peak = compiled.memory_analysis().peak_memory_in_bytes
        assert peak < V5E_HBM_BYTES

    @pytest.mark.slow
    @pytest.mark.parametrize("name, mosaic", [
        ("gpt2-xl.steady", 20), ("gpt2-xl.fsdp4", 4),
    ])
    def test_full_depth_steps_fit(self, v5e_2x2, compiled_kernels, name,
                                  mosaic):
        compiled = aot.programs(_cell(name), v5e_2x2)["step"]()
        peak = compiled.memory_analysis().peak_memory_in_bytes
        assert peak < V5E_HBM_BYTES, peak
        assert aot.hlo_counts(compiled.as_text())["mosaic_calls"] == mosaic

    @pytest.mark.slow
    @pytest.mark.parametrize("name", ["gpt2-xl.steady", "gpt2-xl.fsdp4"])
    def test_the_reference_and_gradient_programs_compile(
        self, v5e_2x2, compiled_kernels, name
    ):
        todo = aot.programs(_cell(name), v5e_2x2)
        for what in ("ref", "ref_losses", "sys"):
            assert todo[what]().memory_analysis().peak_memory_in_bytes > 0
