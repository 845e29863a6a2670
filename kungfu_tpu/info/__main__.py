"""Print framework, backend, and cluster-env information as JSON."""
from __future__ import annotations

import json
import os
import sys


def main() -> int:
    info = {"framework": "kungfu_tpu", "version": "0.1.0"}
    try:
        import jax

        info["jax"] = jax.__version__
        info["backend"] = jax.default_backend()
        info["devices"] = len(jax.devices())
        info["processes"] = jax.process_count()
    except Exception as e:  # pragma: no cover - backend init failure
        info["jax_error"] = str(e)
    env = {k: v for k, v in sorted(os.environ.items()) if k.startswith("KFT_")}
    info["env"] = env
    from ..platforms import discover

    got = discover()
    if got is not None:
        cluster, self_host = got
        info["platform_cluster"] = {"size": cluster.size(), "self": self_host}
    try:
        print(json.dumps(info, indent=2))
    except BrokenPipeError:  # downstream pager/head closed the pipe
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())
