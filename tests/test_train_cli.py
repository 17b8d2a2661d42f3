"""The training launcher, run in process through ``main(argv)``.

One gossip node (``--mesh 1,1``) is the one-chip placement: the step runs
the node's local update on batches that still carry the node axis.  The
model's size comes from the command line alone, never from the backend.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.train import build_config, main

ONE_NODE = [
    "--arch", "granite-8b", "--reduced", "--mesh", "1,1",
    "--topology", "d_ring", "--steps", "3", "--steps-per-epoch", "3",
    "--seq", "16", "--per-node-batch", "2",
]


def _checksums(tree):
    return [float(np.sum(np.square(np.asarray(x, np.float32))))
            for x in jax.tree.leaves(tree)]


def test_one_node_mesh_trains(capsys):
    res = main(ONE_NODE)
    assert len(res.losses) == len(res.step_seconds) == 3
    assert all(l.shape == (1,) and np.isfinite(l).all() for l in res.losses)
    assert not res.trainer.use_shard_map
    init = res.trainer.init_state(jax.random.PRNGKey(0))
    assert _checksums(res.state.params) != _checksums(init.params)
    out = capsys.readouterr().out
    assert "granite-8b: 2 layers, d_model 256" in out
    assert "3 steps in" in out


def test_layers_flag_sets_depth_in_the_run(capsys):
    res = main(ONE_NODE + ["--steps", "1", "--layers", "1"])
    assert res.trainer.cfg.n_layers == 1
    assert "granite-8b: 1 layers" in capsys.readouterr().out


def test_summary_counts_attention_blocks_by_path(capsys):
    """The step's one trace bills each layer's attention block to the path
    it resolved to; on the CPU that is the reference, and later steps,
    which reuse the executable, add nothing."""
    res = main(ONE_NODE + ["--layers", "3"])
    totals = res.trainer.telemetry.totals
    assert totals["attention.path.reference"] == 3
    assert "attention.path.splash" not in totals
    assert ("attention blocks traced into the step, by path: splash 0, "
            "reference 3, chunked 0, chunked_skip 0") in capsys.readouterr().out


def test_summary_counts_chunked_wkv_blocks(capsys):
    """An RWKV-6 step's one trace bills each layer's block to the chunked
    WKV scan and no block to an attention path."""
    argv = list(ONE_NODE)
    argv[argv.index("granite-8b")] = "rwkv6-1.6b"
    res = main(argv + ["--layers", "2"])
    totals = res.trainer.telemetry.totals
    assert totals["wkv.chunked"] == 2
    assert not any(k.startswith("attention.path.") for k in totals)
    out = capsys.readouterr().out
    assert "WKV blocks traced into the step, chunked: 2" in out
    assert ("attention blocks traced into the step, by path: splash 0, "
            "reference 0, chunked 0, chunked_skip 0") in out


@pytest.mark.parametrize("reduced", [False, True])
@pytest.mark.parametrize("layers", [1, 4])
def test_layers_changes_only_n_layers(reduced, layers):
    full = build_config("granite-8b", reduced=reduced)
    cut = build_config("granite-8b", reduced=reduced, layers=layers)
    assert cut.n_layers == layers
    assert dataclasses.replace(cut, n_layers=full.n_layers) == full


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_config_does_not_depend_on_backend(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = build_config("granite-8b")
    published = get_config("granite-8b")
    assert cfg == published
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff, cfg.vocab) == (
        4096, 32, 8, 14336, 49152
    )
    assert build_config("granite-8b", reduced=True) == dataclasses.replace(
        get_config("granite-8b-reduced"), name="granite-8b"
    )


def test_mesh_larger_than_devices_names_the_fix():
    with pytest.raises(SystemExit, match="xla_force_host_platform_device_count=2"):
        main(ONE_NODE + ["--mesh", "2,1"])


def test_profile_dir_traces_the_program_spans(tmp_path):
    """``--profile-dir`` traces the steps it is given with the program's
    host spans, and writes the step's HLO text, whose op_names carry the
    device scopes."""
    from jax.profiler import ProfileData

    main(ONE_NODE + ["--steps", "2", "--profile-dir", str(tmp_path),
                     "--profile-steps", "0:2"])
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = {e.name for p in ProfileData.from_file(str(xplane)).planes
            if p.name.startswith("/host:") for line in p.lines for e in line.events}
    assert {"repro.train_step", "repro.step.compile", "repro.step.dispatch",
            "repro.data.rows"} <= host
    (text,) = [p.read_text() for p in (tmp_path / "step_hlo").glob("*.txt")]
    for scope in ("model/jvp(", "model/transpose(jvp(", "rematted_computation",
                  "/attention/", "/mlp/", "optimizer/", "norms/"):
        assert scope in text, scope


@pytest.mark.parametrize("steps", ["2", "2:1", "a:b", "-1:2"])
def test_profile_steps_must_be_a_range(tmp_path, steps):
    with pytest.raises(SystemExit, match="--profile-steps"):
        main(ONE_NODE + ["--profile-dir", str(tmp_path), f"--profile-steps={steps}"])
