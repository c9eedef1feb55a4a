"""The Trinity cell: the hand counts of one chip's share (ISSUE 34), what
a step requires (``costs_trinity``), each new reader on made-up summaries
and span files, what ``BENCHMARK.json`` gained (order and prefix only:
what it had keeps its place), the contract on the new configuration, the
plain reference's parts against loops, and the cell's CPU rehearsal
through the launcher."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, contract, costs, costs_trinity
from tests.benchmark.test_bench_harness import _run

ROOT = cells.ROOT
CELL = "trinity-large.train16k"
ATTENTION = 62_914_560      # q, gate, o 3072 x 6144 each; k, v 3072 x 1024
EXPERT = 28_311_552         # 3 x 3072 x 3072
DENSE_LAYER = 176_173_312
EXPERT_LAYER = 318_517_504


def _family(kind):
    return cells.family_module(kind, "trinity")


def _config(**over):
    return {**cells.resolve(CELL, ROOT)["config"], **over}


def _sizes(**over):
    return _family("models").sizes(_config(**over))


# ------------------------------------------------------------------ counts

class TestTheChipsShare:
    def test_the_layers_by_hand(self):
        assert ATTENTION == 3 * 3072 * 6144 + 2 * 3072 * 1024
        norms = 4 * 3072 + 2 * 128
        assert DENSE_LAYER == ATTENTION + norms + 3 * 3072 * 12288
        assert EXPERT_LAYER == (
            ATTENTION + norms + 3072 * 256 + EXPERT + 8 * EXPERT
        )
        z = _sizes()
        assert z["params_per_dense_layer"] == DENSE_LAYER
        assert z["params_per_expert_layer"] == EXPERT_LAYER

    def test_what_the_chip_holds(self):
        z = _sizes()
        outside = 2 * 25_024 * 3072 + 3072
        assert outside == 153_750_528
        assert z["params"] == DENSE_LAYER + 4 * EXPERT_LAYER + outside
        assert z["params"] == 1_603_993_856
        # at 6.03 B a parameter: 57 % of the chip before an activation
        assert 6.03 * z["params"] / 16.91e9 == pytest.approx(0.572, abs=2e-3)
        assert (z["layers"], z["dense_layers"], z["expert_layers"]) == (5, 1, 4)
        assert (z["sliding_layers"], z["full_layers"]) == (4, 1)
        assert (z["heads"], z["kv_heads"], z["head_dim"], z["window"]) == (
            48, 8, 128, 4096)
        assert (z["experts_held"], z["experts_routed"],
                z["experts_per_token"], z["expert_ff"]) == (8, 256, 4, 3072)
        # 16 experts a layer would not fit: 2.51e9 parameters, 15.1 GB
        assert 6.03 * _sizes(num_experts=16)["params"] > 15.1e9

    def test_the_parameters_a_token_passes_through(self):
        z = _sizes()
        dense = ATTENTION + 3 * 3072 * 12288
        routed = ATTENTION + 3072 * 256 + EXPERT + 4 * 8 / 256 * EXPERT
        assert dense == 176_160_768
        assert routed == pytest.approx(95.55e6, rel=1e-4)
        head = 25_024 * 3072
        assert z["matmul_params"] == dense + 4 * routed + head
        assert z["matmul_params"] == pytest.approx(635.2e6, rel=1e-4)

    def test_the_file_holds_the_catalogs_numbers(self):
        catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
        if not os.path.isfile(catalog):
            pytest.skip("no catalog here")
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Large-Preview")
        config = _config()
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"]) == {
            "num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size",
        }
        assert config["reduced_from"] == {
            k: row["config"][k] for k in config["reduced"]
        }
        assert len(config["layer_types"]) == 60     # as published
        assert config["layer_types"][:5] == (
            ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
        )
        assert config["chips_per_layer"] == 32
        for item in ("sandwich norms", "q/k norm", "attention output gate",
                     "position encoding", "expert_bias", "auxiliary loss",
                     "initial values", "mup_enabled"):
            assert item in config["assumed"]
        for words in ("pipeline stages", "1/32", "1,603,993,856"):
            assert words in config["deployment"]

    def test_the_contract_holds_the_new_configuration(self):
        assert contract.check(ROOT) == []
        assert contract.cut(_config(), _config()["reduced"]) == []
        assert contract.kind_of("num_dense_layers") == "layers"
        assert contract.kind_of("num_experts") == "experts"
        assert contract.kind_of("num_shared_experts") == ""
        assert contract.kind_of("moe_intermediate_size") == "width"
        # seven experts held, or shares that do not make up the layer
        for change, words in (
            ({"num_experts": 7}, "under the floor"),
            ({"chips_per_layer": 16}, "do not make up"),
            ({"vocab_size": 20_000}, "under the floor"),
        ):
            config = _config(**change)
            why = contract.cut(config, config["reduced"])
            assert any(words in reason for reason in why), why

    def test_the_program_has_as_many_parameters(self):
        cell = cells.resolve(CELL, ROOT)
        built = _family("models").build(cell["config"], cell["job"])
        cfg = built["cfg"]
        assert cfg.param_count() == 1_603_993_856
        # less the embedding (a gather), the final norm and each
        # layer's six: what sits in a matrix multiplication
        assert cfg.active_param_count() - cfg.vocab_size * cfg.d_model - (
            cfg.d_model + (4 * cfg.d_model + 2 * 128) * 5
        ) == pytest.approx(_sizes()["matmul_params"])
        # four times what a balanced router sends here, every expert's
        # rows on the kernel's tiles of 512
        assert cfg.experts.pair_buffer == 8192
        assert cfg.experts.row_tile(8192) == 512
        assert cfg.experts.expected_pairs(16384) == 2048
        assert cfg.remat and cfg.remat_policy == "nothing"
        assert sum(cfg.attention_pairs(i) for i in range(5)) == 369_115_136

    @pytest.mark.parametrize("change", [
        {"score_func": "softmax"}, {"route_norm": False}, {"n_group": 2},
        {"num_shared_experts": 2}, {"mup_enabled": False},
        {"tie_word_embeddings": True}, {"rms_norm_eps": 1e-6},
        {"num_dense_layers": 2}, {"model_type": "llama"},
        {"layer_types": ["chunked_attention"] * 60},
    ])
    def test_build_refuses_what_the_program_does_not_compute(self, change):
        job = cells.resolve(CELL, ROOT)["job"]
        with pytest.raises(ValueError, match="afmoe layer"):
            _family("models").build(_config(**change), job)


# ------------------------------------------------------------------- costs

class TestRequiredWork:
    def test_pairs_by_hand(self):
        p = costs_trinity.pairs(16384, 4096)
        assert p == {"sliding": 58_722_304, "full": 134_225_920}
        assert p["sliding"] / p["full"] == pytest.approx(0.4375, abs=1e-3)
        assert costs_trinity.pairs_per_head(_sizes(), 16384) == 369_115_136
        # 6 positions, window 4: 1 + 2 + 3 + 4 + 4 + 4
        assert costs_trinity.pairs(6, 4) == {"sliding": 18, "full": 21}
        # a sequence inside one window is causal
        assert costs_trinity.pairs(8, 16) == {"sliding": 36, "full": 36}

    def test_the_counts_are_the_masks(self):
        from dlrover_tpu.ops.attention import AttentionMask

        for seq, window in ((6, 4), (96, 32), (16384, 4096)):
            p = costs_trinity.pairs(seq, window)
            assert p["sliding"] == AttentionMask(
                window=window, sliding=True).pairs(seq, seq)
            assert p["full"] == AttentionMask().pairs(seq, seq)

    def test_a_token_and_a_step(self):
        z = _sizes()
        attention = costs_trinity.attention_flops_per_token(z, 16384)
        assert attention == 12 * 128 * 48 * 369_115_136 / 16384
        assert attention == pytest.approx(1.66e9, rel=1e-3)
        token = costs_trinity.train_flops_per_token(z, 16384)
        assert token == 6 * z["matmul_params"] + attention
        assert token == pytest.approx(5.47e9, rel=1e-3)
        assert 16384 * token == pytest.approx(89.7e12, rel=1e-3)
        assert attention / token == pytest.approx(0.30, abs=5e-3)
        # what costs.py would count: five full causal layers
        assert costs.attention_flops_per_token(z, 16384) > 1.8 * attention

    def test_the_kernels_by_hand(self):
        z = {"heads": 2, "head_dim": 4, "window": 4,
             "sliding_layers": 2, "full_layers": 1}
        flops, bytes_ = costs_trinity.flash_attention_cost(
            "fwd", "sliding", 1, z, 6)
        assert flops == 2 * 2 * 18 * (4 + 4)
        assert bytes_ == 2 * 6 * (4 * 4 * 2 + 4)           # q k v o | lse
        flops, bytes_ = costs_trinity.flash_attention_cost(
            "dq", "full", 3, z, 6)
        assert flops == 2 * 6 * 21 * (2 * 4 + 4)
        assert bytes_ == 6 * 6 * (5 * 4 * 2 + 2 * 4)
        # a full layer's call is costs.py's count
        for kind in ("fwd", "dq", "dkv"):
            assert costs_trinity.flash_attention_cost(
                kind, "full", 3, z, 16
            ) == costs.flash_attention_cost(kind, 3, 2, 16, 4)
        a_pass = costs_trinity.flash_attention_step_cost("dkv", 1, z, 6)
        assert len(a_pass) == 3 and a_pass[0] == a_pass[1] != a_pass[2]

    def test_the_grouped_matmul_over_the_buffer(self):
        flops, bytes_ = costs_trinity.grouped_matmul_cost(_sizes(), 8192)
        assert flops == 2 * 8192 * 3072 * 3072
        assert bytes_ == 2 * (2 * 8192 * 3072 + 8 * 3072 * 3072)
        assert flops / 197e12 > bytes_ / 819e9          # compute-bound
        # what the buffer computes a step (three products forward and six
        # backward over 8192 rows, four layers: the issue's 5.6 TFLOP of
        # an 89.7 TFLOP step) against what a uniform router's 2048 pairs
        # a layer require
        assert 8192 * EXPERT * 6 * 4 == pytest.approx(5.57e12, rel=0.01)
        assert 2048 * EXPERT * 6 * 4 == pytest.approx(1.39e12, rel=0.01)


# -------------------------------------------------------------- the readers

def _reader(name):
    return cells.load_module(os.path.join(
        cells.HERE, "layer_metrics", name + ".py"
    )).read


class TestReaders:
    def _ctx(self, summary, tmp_path=None):
        cell = cells.resolve(CELL, ROOT)
        cell["out"] = str(tmp_path) if tmp_path else ""
        return types.SimpleNamespace(
            summary=summary, costs=costs, cell=cell,
            peaks=costs.load_peaks("TPU v5 lite"),
            sizes=_family("models").sizes(cell["config"]),
        )

    def test_mfu_is_required_flops_over_the_step_and_the_peak(self):
        read = _reader("trinity.mfu_device")
        ctx = self._ctx({"step_span_s": [1.0, 1.2, 1.1], "n_devices": 1})
        flops = 16384 * costs_trinity.train_flops_per_token(ctx.sizes, 16384)
        assert read(ctx) == pytest.approx(100 * flops / 1.1 / 197e12)
        assert 40 < read(ctx) < 43          # 89.7 TFLOP in 1.1 s
        assert read(self._ctx(None)) is None
        assert read(self._ctx({"step_span_s": [], "n_devices": 1})) is None

    def test_the_attention_roofline_takes_the_layers_mix(self):
        read = _reader("trinity.attention_roofline")
        # two steps under remat `nothing`: the forward runs twice a layer
        ops = {"flash_attention.fwd": {"count": 20, "self_s": 0.30},
               "flash_attention.dq": {"count": 10, "self_s": 0.20},
               "flash_attention.dkv": {"count": 10, "self_s": 0.25},
               "mosaic.unknown": {"count": 96, "self_s": 0.1}}
        ctx = self._ctx({"ops": ops, "n_devices": 1})
        least = 0.0
        for kind, passes in (("fwd", 4), ("dq", 2), ("dkv", 2)):
            for layer, n in (("sliding", 4), ("full", 1)):
                flops, bytes_ = costs_trinity.flash_attention_cost(
                    kind, layer, 1, ctx.sizes, 16384
                )
                assert flops / 197e12 > bytes_ / 819e9
                least += passes * n * flops / 197e12
        assert read(ctx) == pytest.approx(100 * least / 0.75)
        assert read(ctx) < 100
        assert read(self._ctx({"ops": {}, "n_devices": 1})) is None
        assert read(self._ctx(None)) is None

    OPS = {
        "mosaic.unknown": {"count": 96, "self_s": 0.12,
                           "category": "mosaic"},
        "flash_attention.fwd": {"count": 20, "self_s": 0.3,
                                "category": "mosaic"},
        "fusion.7 fusion bf16[8192,3072]": {
            "count": 8, "self_s": 0.02, "category": "other"},
        "gather.1 gather bf16[8192,3072]": {
            "count": 8, "self_s": 0.01, "category": "data_movement"},
        "fusion.9 fusion bf16[16384,3072]": {
            "count": 8, "self_s": 0.5, "category": "matmul"},
    }

    def test_the_routed_paths_share_of_the_busy_time(self):
        read = _reader("moe.time_share")
        ctx = self._ctx({"ops": self.OPS, "busy_s": 2.0})
        assert read(ctx) == pytest.approx(100 * 0.15 / 2.0)
        plain = {k: v for k, v in self.OPS.items() if "16384" in k}
        assert read(self._ctx({"ops": plain, "busy_s": 2.0})) is None
        assert read(self._ctx(None)) is None

    def test_the_grouped_matmuls_roofline(self):
        read = _reader("moe.grouped_matmul_roofline")
        ctx = self._ctx({"ops": self.OPS})
        each = 2 * 8192 * 3072 * 3072 / 197e12
        assert read(ctx) == pytest.approx(100 * 96 * each / 0.12)
        assert 60 < read(ctx) < 65
        assert read(self._ctx({"ops": {}})) is None
        assert read(self._ctx(None)) is None

    def _file(self, tmp_path, events):
        with open(tmp_path / "agent_trace.worker0.0.jsonl", "w") as f:
            for name, args in events:
                f.write(json.dumps({"name": name, "ph": "C", "ts": 1.0,
                                    "args": args}) + "\n")

    def test_the_counters_are_read_as_the_file_ends(self, tmp_path):
        self._file(tmp_path, [
            ("attn.pairs", {"kind=allowed,seq=16384": 10}),
            ("moe.load_max_over_mean", {"value": 3.0}),
            ("moe.pairs", {"kind=buffer": 16384}),
            ("moe.pairs", {"kind=buffer": 16384, "kind=held": 6192}),
            ("moe.load_max_over_mean", {"value": 7.0}),
            ("moe.pairs", {"kind=buffer": 16384, "kind=held": 2000}),
            ("moe.pairs", {"kind=buffer": 16384, "kind=held": 4096}),
        ])
        ctx = self._ctx(None, tmp_path)
        assert _reader("moe.padding_share")(ctx) == pytest.approx(75.0)
        assert _reader("moe.load_max_over_mean")(ctx) == pytest.approx(3.5)

    def test_a_program_without_the_counters_gives_nothing(self, tmp_path):
        for name in ("moe.padding_share", "moe.load_max_over_mean"):
            assert _reader(name)(self._ctx(None, tmp_path)) is None
        self._file(tmp_path, [("attn.pairs", {"kind=allowed,seq=16384": 1})])
        for name in ("moe.padding_share", "moe.load_max_over_mean"):
            assert _reader(name)(self._ctx(None, tmp_path)) is None


# --------------------------------------------------------- BENCHMARK.json

class TestWhatTheFileGained:
    @pytest.fixture(scope="class")
    def bench(self):
        return cells.load_benchmark(ROOT)

    def test_the_configuration_and_its_cell_follow_what_was_there(
        self, bench
    ):
        # Order only: what PR 30 left comes first. What later PRs append
        # is theirs.
        names = [c["name"] for c in bench["configs"]]
        assert names[:3] == ["gpt2-xl", "mistral-7b-v0.3", "evabyte"]
        config = bench["configs"][names.index("trinity-large-preview")]
        assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                     "num_experts", "vocab_size"]
        cell_names = [w["name"] for w in bench["workloads"]]
        assert cell_names[:5] == [
            "gpt2-xl.steady", "gpt2-xl.elastic", "mistral-7b.long16k",
            "gpt2-xl.fsdp4", "evabyte.train32k",
        ]
        cell = bench["workloads"][cell_names.index(CELL)]
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            "trinity-large-preview", "b1s16k-adam8bit-pairs8k", 1)

    def test_the_cells_metrics(self, bench):
        cell = cells.resolve(CELL, ROOT)
        assert {m["name"] for m in cell["end_to_end"]} == {
            "tokens_per_s", "setup_s"}
        listed = {m["name"] for m in cell["per_layer"]}
        new = {"trinity.mfu_device", "trinity.attention_roofline",
               "moe.time_share", "moe.grouped_matmul_roofline",
               "moe.padding_share", "moe.load_max_over_mean"}
        assert new | {"eva.masked_pair_share", "flash_attention_time_share",
                      "adam8bit_roofline", "device.idle_share",
                      "model.step_device_ms", "trainer.host_ms"} <= listed
        # counted with other pairs: not this cell's
        assert not {"model.mfu_device", "flash_attention_roofline",
                    "eva.mfu_device", "eva.attention_roofline"} & listed
        entries = {m["name"]: m for m in bench["per_layer"]}
        for name in new:
            assert entries[name]["workloads"][0] == CELL
            assert entries[name]["moves"] == "tokens_per_s"
        # every list the cell joined still starts with the cells it had
        for m in bench["per_layer"] + bench["end_to_end"]:
            if CELL in m.get("workloads", []) and m["name"] not in new:
                assert m["workloads"].index(CELL) >= (
                    m["workloads"].index("evabyte.train32k") + 1
                )

    def test_the_job_is_the_issues(self):
        job = cells.resolve(CELL, ROOT)["job"]
        assert (job["batch"], job["sequence"], job["param_dtype"]) == (
            1, 16384, "bfloat16")
        assert job["optimizer"]["factory"].endswith(":adam8bit")
        assert job["optimizer"]["args"] == {"learning_rate": 0.0002}
        assert job["attention"] == {"impl": "pallas", "block_q": 1024,
                                    "block_k": 1024}
        assert job["moe"] == {"pair_buffer": 8192}      # as the issue names
        assert not job["checkpoint"]["enabled"] and not job["kill"]
        old = cells.resolve("mistral-7b.long16k", ROOT)["job"]
        for key in ("launcher", "data", "warmup_steps", "trace_steps"):
            assert job[key] == old[key]
        # past one window: the gradient sample runs a window that slides
        window = cells.resolve(CELL, ROOT)["config"]["sliding_window"]
        assert job["reference"]["grad_sample_tokens"] > window
        toy = cells.resolve(CELL, ROOT, rehearsal=True)
        assert toy["config"]["reduced_from"]["num_experts"] == 16
        assert toy["config"]["num_experts"] == 8
        assert toy["config"]["num_key_value_heads"] == 2
        assert toy["config"]["sliding_window"] == 32
        assert toy["job"]["sequence"] >= 4 * toy["config"]["sliding_window"]
        assert toy["job"]["moe"] == {"pair_buffer": 1024}


# ---------------------------------------------------- the plain reference

class TestTheReference:
    def test_attention_is_the_equations_pair_by_pair(self):
        ref = _family("reference")
        s, h, g, d, w = 24, 4, 2, 4, 8
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (1, s, h, d))
        k, v = (jax.random.normal(x, (1, s, g, d)) for x in keys[1:])
        for window in (0, w):
            got = ref.grouped_attention(q, k, v, window, query_block=8)
            q64, k64, v64 = (np.asarray(x, np.float64) for x in (q, k, v))
            for head in range(h):
                kv = head // (h // g)
                for i in range(s):
                    seen = [j for j in range(s) if j <= i
                            and (not window or j > i - window)]
                    scores = np.array(
                        [q64[0, i, head] @ k64[0, j, kv] / 2 for j in seen])
                    p = np.exp(scores - scores.max())
                    p /= p.sum()
                    want = sum(pj * v64[0, j, kv] for pj, j in zip(p, seen))
                    np.testing.assert_allclose(got[0, i, head], want,
                                               atol=1e-5)

    def test_the_held_experts_are_the_equations_token_by_token(self):
        ref = _family("reference")
        n, d, f, routed, held, top = 12, 6, 5, 8, 3, 2
        keys = jax.random.split(jax.random.PRNGKey(1), 8)
        x = jax.random.normal(keys[0], (n, d))
        p = {"router": jax.random.normal(keys[1], (d, routed)),
             "w_gate": jax.random.normal(keys[2], (held, d, f)),
             "w_up": jax.random.normal(keys[3], (held, d, f)),
             "w_down": jax.random.normal(keys[4], (held, f, d)),
             "shared_gate": jax.random.normal(keys[5], (d, f)),
             "shared_up": jax.random.normal(keys[6], (d, f)),
             "shared_down": jax.random.normal(keys[7], (f, d))}
        config = {"num_experts_per_tok": top, "route_scale": 2.448}
        with jax.default_matmul_precision("highest"):
            got = ref.held_experts(x, p, config)
        x64 = np.asarray(x, np.float64)
        p64 = {k: np.asarray(w, np.float64) for k, w in p.items()}
        silu = lambda a: a / (1 + np.exp(-a))
        # the bias: the recurrence of the file's head, round by round
        scores = 1 / (1 + np.exp(-(x64 @ p64["router"])))
        bias = np.zeros(routed)
        for i in range(64):
            chosen = np.argsort(-(scores + bias), axis=-1)[:, :top]
            load = np.bincount(chosen.ravel(), minlength=routed)
            bias = bias + 0.1 * 0.9 ** i * np.sign(n * top / routed - load)
        assert np.abs(bias).max() > 0.05        # it moves the choice
        for t in range(n):
            s = scores[t]
            chosen = np.argsort(-(s + bias))[:top]
            want = (silu(x64[t] @ p64["shared_gate"])
                    * (x64[t] @ p64["shared_up"])) @ p64["shared_down"]
            for e in chosen:
                if e < held:        # the others are other chips'
                    out = (silu(x64[t] @ p64["w_gate"][e])
                           * (x64[t] @ p64["w_up"][e])) @ p64["w_down"][e]
                    want = want + 2.448 * s[e] / (s[chosen].sum() + 1e-20) * out
            np.testing.assert_allclose(got[t], want, rtol=2e-4, atol=2e-4)

    def test_the_tolerance_is_stated(self):
        tol = _family("reference").TOLERANCE
        assert set(tol) == {"loss_abs", "layer0", "embed"}
        for group in ("layer0", "embed"):
            lo, hi = tol[group]["norm_ratio"]
            assert lo < 1 < hi and 0.99 < tol[group]["cosine_min"] < 1


# ------------------------------------------------------------- the launcher

def test_cpu_rehearsal_of_the_cell_through_the_launcher():
    """The whole control flow at toy widths, traced: launcher, fork
    server, worker, reference comparison, window; the step's counters
    reach the worker's file and their readers, no device metric is
    printed."""
    r = _run(["--workload", CELL, "--seed", "2147483659", "--seconds", "1",
              "--trace", "1", "--rehearsal"])
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "cpu" and line["correct"], r.stderr[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"moe.padding_share", "moe.load_max_over_mean",
            "eva.masked_pair_share", "trainer.host_ms"} <= set(
        line["reported"])
    assert not {"trinity.mfu_device", "moe.time_share"} & set(
        line["reported"])
