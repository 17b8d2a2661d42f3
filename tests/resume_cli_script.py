"""Subprocess body for test_spmd.py: crash-consistent --resume round-trip.

Drives the real launcher (``repro.launch.train.main``) three times in one
process: (1) an uninterrupted faulted closed-loop-Ada run to step 8,
(2) the same run stopped at step 4 with a checkpoint, (3) ``--resume`` of
that checkpoint to step 8.  The step-8 checkpoints of (1) and (3) must be
BIT-identical — every parameter/optimizer array and the JSON extra payload
(controller transitions/events/trace + membership tracking): fault
realizations are pure fn(seed, step), data and lr are step-keyed, so an
interrupted run replays exactly.

Second round-trip: a spare-rank pool run whose checkpoint lands BEFORE the
join activates a ghost rank and whose resume crosses the activation —
membership tracking and the seeded SparePool stream must replay the
activation identically.  Finally: a mismatched-config ``--resume``
(different topology) must fail fast with the recorded-vs-configured error,
not a mid-restore shape mismatch.
"""
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch import train


def run(argv):
    train.main(argv)


base = tempfile.mkdtemp(prefix="resume_cli_")
dir_a = os.path.join(base, "uninterrupted")
dir_b = os.path.join(base, "interrupted")
common = [
    "--arch", "granite-8b", "--reduced",
    "--topology", "d_ada", "--k-floor", "one_peer",
    "--consensus-target", "0.5",
    "--fault-model", "dropout", "--fault-rate", "0.35", "--fault-seed", "3",
    "--steps-per-epoch", "10", "--seq", "16", "--per-node-batch", "2",
    "--mesh", "4,2", "--ckpt-every", "4",
]

run(common + ["--steps", "8", "--ckpt-dir", dir_a])
run(common + ["--steps", "4", "--ckpt-dir", dir_b])
run(common + ["--steps", "8", "--ckpt-dir", dir_b, "--resume"])

ckpt = "step_0000000008.npz"
da = np.load(os.path.join(dir_a, ckpt))
db = np.load(os.path.join(dir_b, ckpt))
assert set(da.files) == set(db.files), (
    sorted(set(da.files) ^ set(db.files))
)
assert "__extra__" in da.files  # the engine run state rode along
bad = [k for k in da.files if not np.array_equal(da[k], db[k])]
assert not bad, f"resume diverged on: {bad[:10]}"
print(f"compared {len(da.files)} arrays (incl. controller/membership extra)")

# --- round-trip crossing a spare-rank activation ---------------------------
# ckpt at step 4, the pre-declared join activates the ghost rank at step 6:
# the resumed half replays the activation (adopt + membership re-arm) from
# the seeded stream alone and must land bit-identical at step 8.
dir_c = os.path.join(base, "spare_uninterrupted")
dir_d = os.path.join(base, "spare_interrupted")
spare = [
    "--arch", "granite-8b", "--reduced",
    "--topology", "d_ada", "--k-floor", "one_peer",
    "--consensus-target", "0.5",
    "--fault-model", "join", "--fault-join-steps", "6",
    "--spare-ranks", "1", "--fault-seed", "5",
    "--steps-per-epoch", "10", "--seq", "16", "--per-node-batch", "2",
    "--mesh", "4,2", "--ckpt-every", "4",
]
run(spare + ["--steps", "8", "--ckpt-dir", dir_c])
run(spare + ["--steps", "4", "--ckpt-dir", dir_d])
run(spare + ["--steps", "8", "--ckpt-dir", dir_d, "--resume"])
dc = np.load(os.path.join(dir_c, ckpt))
dd = np.load(os.path.join(dir_d, ckpt))
assert set(dc.files) == set(dd.files)
bad = [k for k in dc.files if not np.array_equal(dc[k], dd[k])]
assert not bad, f"spare-activation resume diverged on: {bad[:10]}"
print(f"compared {len(dc.files)} arrays across the spare activation")

# --- fail-fast config validation -------------------------------------------
# resuming the dir_b checkpoint under a different topology must raise the
# recorded-vs-configured error, not an opaque restore failure
try:
    run([
        "--arch", "granite-8b", "--reduced", "--topology", "d_ring",
        "--steps-per-epoch", "10", "--seq", "16", "--per-node-batch", "2",
        "--mesh", "4,2", "--steps", "8",
        "--ckpt-dir", dir_b, "--resume",
    ])
    raise SystemExit("mismatched --resume should have failed fast")
except ValueError as e:
    assert "resume config mismatch" in str(e), e
    assert "d_ada" in str(e) and "d_ring" in str(e), e
    print(f"fail-fast resume: {e}")

print("RESUME_ROUNDTRIP_OK")
