"""The benchmark's yardstick arithmetic against hand counts, the published
parameter counts, and ``BENCHMARK.json`` against its contract."""

import json
import os
import re

import pytest

from benchmark import cells, costs

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def _sizes(config_name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    family = cells.family_module("models", config["family"])
    return config, family.sizes(config)


class TestPublishedCounts:
    def test_gpt2_xl_is_the_published_1557_6_million(self):
        config, sizes = _sizes("gpt2-xl")
        assert sizes["params"] == 1_557_611_200
        assert (config["n_layer"], config["n_embd"], config["n_head"]) == (
            48, 1600, 25
        )
        assert sizes["head_dim"] == 64 and config["reduced"] == []

    def test_mistral_layer_and_embeddings(self):
        config, sizes = _sizes("mistral-7b-v0.3")
        assert sizes["params_per_layer"] == 218_112_000
        embed_and_head = 2 * config["vocab_size"] * config["hidden_size"]
        assert embed_and_head == 268_435_456
        assert sizes["params"] == (
            config["num_hidden_layers"] * 218_112_000 + embed_and_head
            + config["hidden_size"]
        )
        # Depth is the only cut; at the published depth it is the 7.25B.
        assert config["reduced"] == ["num_hidden_layers"]
        assert config["reduced_from"] == {"num_hidden_layers": 32}
        assert 32 * 218_112_000 + embed_and_head + 4096 == 7_248_023_552


class TestRequiredWork:
    def test_train_flops_of_a_one_layer_model_by_hand(self):
        # d 8, 2 heads of 4, one layer, 100 matmul weights, sequence 3:
        # weights 6 * 100; attention: queries see 1, 2, 3 keys = 2 on
        # average; forward 4 FLOPs a pair and head dimension, * 8 wide
        # = 64 a token, backward twice that.
        sizes = {"heads": 2, "head_dim": 4, "layers": 1,
                 "matmul_params": 100}
        assert costs.attention_flops_per_token(sizes, 3) == 3 * 64
        assert costs.train_flops_per_token(sizes, 3) == 600 + 192

    def test_gpt2_xl_and_mistral_per_token(self):
        _, xl = _sizes("gpt2-xl")
        # 6 * 1554.97e6 weights + 6 * 48 * 1600 * 1025
        assert costs.train_flops_per_token(xl, 1024) == pytest.approx(
            9.802e9, rel=1e-3
        )
        _, mistral = _sizes("mistral-7b-v0.3")
        total = costs.train_flops_per_token(mistral, 16384)
        assert total == pytest.approx(7.650e9, rel=1e-3)
        share = costs.attention_flops_per_token(mistral, 16384) / total
        assert share == pytest.approx(0.21, abs=0.005)
        share_xl = costs.attention_flops_per_token(xl, 1024) / (
            costs.train_flops_per_token(xl, 1024)
        )
        assert share_xl == pytest.approx(0.048, abs=0.002)

    def test_flash_attention_kernels_by_hand(self):
        # batch 1, 1 head, sequence 4, head dimension 2, 2-byte values:
        # 10 causal pairs; a matmul is 2 * pairs * 2 = 40 FLOPs.
        flops, bytes_ = costs.flash_attention_cost("fwd", 1, 1, 4, 2)
        assert flops == 2 * 40
        # q k v read, o written: 4 tensors of 16 bytes; lse 4 rows * 4
        assert bytes_ == 4 * 16 + 16
        dq = costs.flash_attention_cost("dq", 1, 1, 4, 2)
        dkv = costs.flash_attention_cost("dkv", 1, 1, 4, 2)
        assert dq[0] + dkv[0] == 5 * 40      # one backward: 5 matmuls
        assert dq[1] == 5 * 16 + 2 * 16 and dkv[1] == 6 * 16 + 2 * 16

    def test_adam8bit_bytes_by_hand(self):
        # 512 bf16 parameters, block 256: gradient 2 + parameter read and
        # written 4 + two int8 moments read and written 4 = 10 bytes
        # each; 2 moments * 2 blocks * 4-byte scales read and written.
        flops, bytes_ = costs.adam8bit_cost(512, 2, 2)
        assert bytes_ == 512 * 10 + 2 * (2 * 2 * 4)
        assert flops == 24 * 512

    def test_roofline_says_which_peak_bounds(self):
        peaks = costs.load_peaks("TPU v5 lite")
        assert costs.roofline_seconds(197e12, 1.0, peaks) == (1.0, "flops")
        assert costs.roofline_seconds(1.0, 819e9, peaks) == (1.0, "bytes")


class TestPeaks:
    def test_v5e_peaks_are_the_published_ones(self):
        for kind in ("TPU v5 lite", "TPU v5e"):
            p = costs.load_peaks(kind)
            assert p["bf16_flops_per_s"] == 197e12
            assert p["hbm_bytes_per_s"] == 819e9
            assert p["hbm_bytes"] == 16e9
            assert p["ici_bits_per_s"] == 1600e9

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source", ""])
    def test_an_unknown_device_is_an_error(self, kind):
        with pytest.raises(KeyError):
            costs.load_peaks(kind)


class TestContract:
    """What the driver refuses before a single run."""

    @pytest.fixture(scope="class")
    def bench(self):
        return cells.load_benchmark(ROOT)

    def test_keys_and_sizes(self, bench):
        assert set(bench) == {
            "command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer",
        }
        assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
        assert 1 <= bench["run_seconds"] <= 51
        assert len(bench["command"]) <= 32
        assert 1 <= len(bench["paths"]) <= 16
        assert 2 <= len(bench["workloads"]) <= 24
        assert 1 <= len(bench["end_to_end"]) <= 16
        assert 1 <= len(bench["per_layer"]) <= 128

    def test_names_are_plain_and_used_once(self, bench):
        for group in ("configs", "workloads"):
            names = [x["name"] for x in bench[group]]
            assert len(set(names)) == len(names)
            assert all(NAME.match(n) for n in names)
            assert all(len(x["why"]) <= 200 for x in bench[group])
        metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
        assert len(set(metrics)) == len(metrics)
        assert all(NAME.match(n) for n in metrics)

    def test_every_config_is_a_file_under_paths_used_by_a_cell(self, bench):
        used = {w["config"] for w in bench["workloads"]}
        files = set()
        for c in bench["configs"]:
            assert c["name"] in used
            assert any(c["file"].startswith(p + "/") for p in bench["paths"])
            with open(os.path.join(ROOT, c["file"])) as f:
                config = json.load(f)
            assert config["reduced"] == c["reduced"]
            assert config["source"] == c["source"]
            assert c["source"].startswith("https://")
            assert c["file"] not in files
            files.add(c["file"])
            # No width may be reduced.
            for key in c["reduced"]:
                assert not re.search(
                    r"(_size$|_dim$|_rank$|head|embd|inner|per_tok)", key
                ), key

    def test_cells(self, bench):
        pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
        assert len(set(pairs)) == len(pairs)
        assert all(w["chips"] in (1, 4) for w in bench["workloads"])
        four = sum(w["chips"] == 4 for w in bench["workloads"])
        assert four <= max(1, len(bench["workloads"]) // 4)

    def test_metrics(self, bench):
        sources = {"device_trace", "program_span", "program_counter",
                   "host_clock"}
        cells_ = {w["name"] for w in bench["workloads"]}
        end = {m["name"]: m for m in bench["end_to_end"]}
        assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
        for m in bench["end_to_end"]:
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
            assert m["better"] in ("higher", "lower")
        for m in bench["per_layer"]:
            assert m["source"] in sources and m["moves"] in end
            assert m["better"] in ("higher", "lower") and m["layer"]
            assert "bound" not in m
        for m in bench["end_to_end"] + bench["per_layer"]:
            assert set(m.get("workloads", [])) <= cells_

    def test_a_per_layer_metric_is_listed_only_where_what_it_moves_is(
        self, bench
    ):
        # The driver refuses the file otherwise, before any run: a metric
        # without a ``workloads`` list is reported in every cell.
        cells_ = [w["name"] for w in bench["workloads"]]
        end = {m["name"]: m for m in bench["end_to_end"]}
        for m in bench["per_layer"]:
            moved = end[m["moves"]].get("workloads", cells_)
            assert set(m.get("workloads", cells_)) <= set(moved), m["name"]
        # ... and the harness reports exactly what the file lists.
        for name in cells_:
            listed = {m["name"] for m in bench["per_layer"]
                      if name in m.get("workloads", cells_)}
            got = {m["name"] for m in cells.resolve(name, ROOT)["per_layer"]}
            assert got == listed, name

    def test_every_cell_reports_setup_another_metric_and_a_layer(self, bench):
        for w in bench["workloads"]:
            cell = cells.resolve(w["name"], ROOT)
            names = {m["name"] for m in cell["end_to_end"]}
            assert "setup_s" in names and len(names) >= 2
            assert cell["per_layer"]

    def test_every_per_layer_metric_has_its_own_reader(self, bench):
        for m in bench["per_layer"]:
            path = os.path.join(ROOT, "benchmark", "layer_metrics",
                                m["name"] + ".py")
            assert callable(cells.load_module(path).read), m["name"]
