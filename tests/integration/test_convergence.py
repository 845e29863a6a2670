"""The convergence-comparison harness must run every optimizer family in one
command (reference README.md:191-197 analog): the table on stdout, the files
only where asked for."""
import json
import subprocess
import sys


def test_convergence_harness_all_families(tmp_path):
    out = tmp_path / "conv.json"
    md = tmp_path / "conv.md"
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.testing.convergence",
            "--steps", "60", "--log-every", "20",
            "--out", str(out), "--markdown", str(md),
        ],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(out.read_text())
    names = {x["optimizer"] for x in doc["results"]}
    assert names == {
        "ssgd", "sma", "gossip-random", "gossip-roundrobin", "ada",
        "gossip-host", "gossip-host-overlapped",
    }
    for x in doc["results"]:
        # every family must beat 10-class chance decisively
        assert x["eval_accuracy"] > 0.5, x
        assert x["loss_curve"][-1][1] < x["loss_curve"][0][1], x
    assert "| ssgd |" in md.read_text()
    assert md.read_text() == r.stdout
