"""Paper Fig 3 analogue: strong scaling — fixed model, growing worker count.

Host devices stand in for CPUs/chips (subprocess per device count since JAX
locks the device count at first init).  The caller must not hold the device
while the children run; a child that fails fails the sweep."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_CHILD = textwrap.dedent("""
    import json, sys
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core.engine import AXIS
    sys.path.insert(0, "benchmarks")
    from common import build, throughput

    n = int(sys.argv[1])
    assert len(jax.devices()) >= n, f"{len(jax.devices())} devices, need {n}"
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))
    eng = build(o=512, m=20, s=256, lookahead=0.5, dist="exponential",
                mesh=mesh)
    ev_s, nev, dt, clean = throughput(eng, warmup_epochs=5, epochs=25)
    print(json.dumps({"ev_s": ev_s, "n": nev, "dt": dt, "clean": clean}))
""")


def run(rows):
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={n}").strip()
        env["PYTHONPATH"] = "src"
        r = subprocess.run([sys.executable, "-c", _CHILD, str(n)], env=env,
                           capture_output=True, text=True, timeout=1200)
        if r.returncode != 0:
            raise RuntimeError(f"fig3 scaling child W={n} failed:\n"
                               f"{r.stderr[-2000:]}")
        d = json.loads(r.stdout.strip().splitlines()[-1])
        rows.append({
            "name": f"fig3_scaling_W{n}",
            "us_per_call": 1e6 * d["dt"] / max(d["n"], 1),
            "derived": f"events_per_s={d['ev_s']:.0f} clean={d['clean']}",
        })
    return rows
