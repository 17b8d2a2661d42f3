"""The benchmark's files parse, agree with BENCHMARK.json, and a cell added
as a file is run with no code edit."""
from __future__ import annotations

import json

import pytest

from chipbench_util import BENCH, REPO, SRC, run_args, tiny_bench

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
WORKLOADS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_parses(name):
    from benchlib.files import Bench

    bench = Bench(BENCH)
    cfg = bench.config(name)
    for key in ("arch", "family", "source", "dtype", "hidden_size",
                "intermediate_size", "num_hidden_layers", "vocab_size"):
        assert key in cfg, key
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["here"] != cut["published"]
    ref = bench.reference(cfg["family"])
    assert set(ref.program_sizes(cfg)) <= {"n_layers", "d_model", "n_heads", "n_kv",
                                           "d_ff", "vocab", "rope_theta"}
    assert bench.flops(cfg["family"]).flops_per_token(cfg, 4096) > 0


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_file_parses(name):
    from benchlib.files import Bench

    bench = Bench(BENCH)
    w = bench.workload(name)
    bench.config(w["config"])
    t = bench.traffic(w["traffic"])
    assert t["seq"] > 0 and t["per_node_batch"] > 0 and 0 <= t["structure"] <= 1
    assert w["mesh"][0] * w["mesh"][1] == w["chips"]
    if name in {c["name"] for c in BENCHMARK["workloads"]}:
        assert set(w["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
        assert all(v > 0 for v in w["limits"].values())
    else:   # limits come only from readings on the chip
        assert "limits" not in w and w["status"]


def test_cell_without_limits_is_refused(tmp_path):
    import run
    from benchlib.files import BenchError

    root = tiny_bench(tmp_path, "granite-1chip-s4096", limits=None)
    with pytest.raises(BenchError, match="no limits"):
        run.run(run_args(), root=root, src=SRC, require_tpu=False,
                compile_cache=False)


@pytest.mark.parametrize("seed", [0, 3000000201, 2**33 + 5])
def test_reference_rows_equal_program_rows(seed):
    """The window's rows come from the program's generator, the reference's
    from the yardstick's copy of it: the same rows for every seed."""
    from benchlib import data
    from repro.data import SyntheticLM

    traffic = {"seq": 96, "per_node_batch": 2, "structure": 0.85}
    for step in (0, 7):
        ours = data.stacked(traffic, 49152, 4, step, seed)
        theirs = SyntheticLM(vocab=49152, seq_len=96, seed=seed,
                             structure=0.85).stacked(4, step, 2)
        for k in ("tokens", "targets"):
            assert ours[k].dtype == theirs[k].dtype
            assert (ours[k] == theirs[k]).all()


def test_benchmark_json_names_existing_files():
    from benchlib.files import Bench

    bench = Bench(BENCH)
    for c in BENCHMARK["configs"]:
        assert (REPO / c["file"]).is_file()
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(bench.config(c["name"])["reduced"])
    for w in BENCHMARK["workloads"]:
        f = bench.workload(w["name"])
        assert (f["config"], f["traffic"], f["chips"]) == (w["config"], w["traffic"], w["chips"])
    for m in BENCHMARK["per_layer"]:
        assert callable(bench.metric(m["name"]).read)
        assert set(m["workloads"]) <= set(WORKLOADS)
    assert "TPU v5 lite" in json.loads((BENCH / "peaks.json").read_text())["devices"]


def test_unknown_device_kind_is_an_error():
    from benchlib.files import Bench, BenchError

    with pytest.raises(BenchError):
        Bench(BENCH).peaks("TPU v9 imaginary")


def test_new_workload_file_runs_without_code_edit(tmp_path):
    """A cell that exists only as new files is found by name and runs end to
    end on the CPU (the chip check skipped), and its check passes."""
    import run

    root = tiny_bench(tmp_path, "granite-1chip-s4096", name="added-cell")
    out = run.run(run_args("added-cell"), root=root, src=SRC,
                  require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s", "step_p90_s"}
    assert list(out)[-1] == "checks"
