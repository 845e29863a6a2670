"""End-to-end LLM showcase: two gpt_train.py processes — dp x sp training
streamed from the C++ file loader, async checkpoint in the first run, a
clean restart that restores and keeps improving, and KV-cache generation.
(Crash-mid-save recovery and the launcher env contract are covered
elsewhere: tests/unit/test_checkpoint.py kill-and-resume drills and
tests/integration/test_launcher.py.)"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(args, timeout=600, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # OVERRIDE, not setdefault: this test is written for the CPU mesh,
    # whatever platform the environment names
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", "gpt_train.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_REPO,
    )


def test_train_checkpoint_resume_generate(tmp_path):
    ck = str(tmp_path / "ck")
    data = str(tmp_path / "tokens")
    common = ["--dp", "4", "--sp", "2", "--batch", "8", "--seq-len", "64",
              "--d-model", "64", "--n-layers", "2", "--vocab", "128",
              "--data", "files", "--data-dir", data, "--ckpt-dir", ck,
              "--ckpt-every", "5"]

    r1 = _run(common + ["--steps", "10"])
    assert r1.returncode == 0, r1.stderr[-800:]
    assert "RESULT: example=gpt_train" in r1.stdout

    # resume from step 10 and finish with generation
    r2 = _run(common + ["--steps", "20", "--generate", "8"])
    assert r2.returncode == 0, r2.stderr[-800:]
    assert "# resumed from step 10" in r2.stdout, r2.stdout[-800:]
    assert "# generated" in r2.stdout

    # loss kept falling THROUGH the restore: the resumed run's final loss
    # must beat the first run's step-10 loss (garbage restore or a dead
    # optimizer would reset toward the ln(vocab)≈4.85 baseline)
    def step_losses(out):
        return [
            float(line.split("loss")[1])
            for line in out.splitlines()
            if line.startswith("# step")
        ]

    l10 = step_losses(r1.stdout)[-1]
    l20 = step_losses(r2.stdout)[-1]
    assert l20 < l10 - 0.05, (l10, l20)

    # generation emits seq-consistent token ids from the trained vocab
    gen_line = [l for l in r2.stdout.splitlines() if l.startswith("# generated")][0]
    toks = json.loads(gen_line.split("generated", 1)[1].strip())
    assert len(toks) == 8 and all(0 <= t < 128 for t in toks)
