"""Distribute/rrun + platform adapters + info (reference kungfu-distribute,
kungfu-rrun, platforms/modelarts, kungfu.info)."""
import os
import json
import subprocess
import sys

from kungfu_tpu.plan import HostList
from kungfu_tpu.platforms import discover, from_generic_env, from_tpu_pod_env
from kungfu_tpu.run.distribute import Distributor, HostResult, rrun

BASH = ("bash", "-c")  # local transport standing in for ssh


class TestDistributor:
    def test_parallel_exec(self, capsys):
        d = Distributor(["h1", "h2", "h3"], transport=BASH)
        results = d.run("echo from-$KFT_DIST_HOST")
        assert [r.returncode for r in results] == [0, 0, 0]
        for host, r in zip(["h1", "h2", "h3"], results):
            assert f"from-{host}" in r.output
        out = capsys.readouterr().out
        assert "[h2] from-h2" in out  # per-host prefixes (reference tee style)

    def test_failure_reported(self):
        d = Distributor(["a", "b"], transport=BASH, prefix_output=False)
        results = d.run("test $KFT_DIST_HOST = a")
        by_host = {r.host: r.returncode for r in results}
        assert by_host["a"] == 0 and by_host["b"] != 0

    def test_extra_env(self):
        d = Distributor(["x"], transport=BASH, prefix_output=False,
                        extra_env={"FOO": "bar baz"})
        r = d.run("echo FOO=$FOO")[0]
        assert "FOO=bar baz" in r.output

    def test_timeout(self):
        d = Distributor(["x"], transport=BASH, prefix_output=False)
        r = d.run("sleep 30", timeout=0.5)[0]
        assert r.returncode == 124


class TestRrun:
    def test_command_shape(self):
        """rrun issues one launcher per host with -self bound to that host."""
        hl = HostList.parse("10.0.0.1:2,10.0.0.2:2")
        results = rrun(hl, 4, ["python", "train.py"], transport=BASH,
                       python="echo python3")
        assert len(results) == 2
        for spec, r in zip(hl, results):
            assert r.returncode == 0
            assert f"-self {spec.host}" in r.output
            assert "-np 4" in r.output and "-H 10.0.0.1:2,10.0.0.2:2" in r.output
            assert "train.py" in r.output


class TestPlatforms:
    def test_tpu_pod_env(self):
        env = {"TPU_WORKER_HOSTNAMES": "t0,t1,t2", "TPU_WORKER_ID": "1"}
        cluster, self_host = from_tpu_pod_env(env)
        assert cluster.size() == 3 and self_host == "t1"

    def test_generic_env(self):
        env = {"KFT_HOSTS": "a:2,b:2", "KFT_NP": "3", "KFT_SELF_HOST": "b"}
        cluster, self_host = from_generic_env(env)
        assert cluster.size() == 3 and self_host == "b"

    def test_discover_order_and_miss(self):
        assert discover({}) is None
        got = discover({"TPU_WORKER_HOSTNAMES": "t0", "KFT_HOSTS": "x:1"})
        assert got is not None and got[1] == "t0"  # TPU adapter wins


def test_info_module():
    # pin cpu: the unit suite must not depend on a chip being there
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.info"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 0, r.stderr[-1000:]
    info = json.loads(r.stdout)
    assert info["framework"] == "kungfu_tpu"
    assert "jax" in info and info["devices"] >= 1


class TestRrunConcurrency:
    def test_hosts_launch_in_parallel(self):
        """Per-host launchers must run concurrently: real jobs rendezvous
        across hosts, so sequential launches deadlock (review regression)."""
        import time

        hl = HostList.parse("h1:1,h2:1,h3:1")
        t0 = time.perf_counter()
        results = rrun(hl, 3, ["x"], transport=BASH, python="sleep 1; echo python3")
        dt = time.perf_counter() - t0
        assert all(r.returncode == 0 for r in results)
        assert dt < 2.5, f"hosts ran sequentially ({dt:.1f}s for 3x sleep 1"
