"""Paper Sec. VI scaling claim ("perfect strong or weak scaling"): per-device
work of the distributed LC-RWMD serve step vs device count.

Wall-clock scaling cannot be demonstrated on a 1-core host, so this harness
does what the dry-run methodology does everywhere else: lower + compile the
SAME serve workload on growing meshes and extract per-device FLOPs / HBM
bytes / collective bytes with the trip-count-aware analyzer. Perfect strong
scaling = per-device compute & memory ~ 1/N with sub-linear collective
growth.  Runs in a subprocess (needs the multi-device XLA flag).
"""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parent.parent

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.launch.hlo_cost import analyze
    from repro.distributed.lcrwmd_dist import build_serve_step
    from repro.data.docs import DocSet
    from jax.sharding import NamedSharding, PartitionSpec as P

    h, b, m, k = 32, 64, 64, 8
    out = {}

    def measure(mesh, n, v):
        serve = build_serve_step(mesh, k=k, bf16_matmul=False)
        sh = lambda *s: NamedSharding(mesh, P(*s))
        sds = lambda shape, dt, s: jax.ShapeDtypeStruct(shape, dt, sharding=s)
        resident = DocSet(ids=sds((n, h), jnp.int32, sh("data", None)),
                          weights=sds((n, h), jnp.float32, sh("data", None)))
        queries = DocSet(ids=sds((b, h), jnp.int32, sh(None, None)),
                         weights=sds((b, h), jnp.float32, sh(None, None)))
        emb = sds((v, m), jnp.float32, sh(("model", "data"), None))
        comp = jax.jit(serve).lower(resident, queries, emb).compile()
        r = analyze(comp.as_text())
        return {"flops_per_dev": r["flops"], "hbm_per_dev": r["hbm_bytes"],
                "coll_per_dev": r["collective_bytes"]}

    # STRONG: fixed problem (n=v=65536), growing mesh.
    for (da, mo) in [(1, 1), (2, 2), (4, 4), (8, 8)]:
        mesh = make_host_mesh(data=da, model=mo)
        out[f"strong_{da}x{mo}"] = dict(
            measure(mesh, 65536, 65536), devices=da * mo)
    # WEAK: per-device resident share constant (n = 8192 * devices).
    for (da, mo) in [(1, 1), (2, 2), (4, 4), (8, 8)]:
        mesh = make_host_mesh(data=da, model=mo)
        ndev = da * mo
        out[f"weak_{da}x{mo}"] = dict(
            measure(mesh, 8192 * ndev, 16384 * ndev), devices=ndev)
    print("JSON:" + json.dumps(out))
""")


def run() -> list:
    from benchmarks.common import BenchResult

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO / "src")
    # Compile-only on virtual CPU devices: the child never takes the chip,
    # which the parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, env=env, timeout=1500)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-3000:])
    data = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("JSON:")][0][5:])
    out = []
    sbase = data["strong_1x1"]
    for mesh, d in sorted(((k_, v) for k_, v in data.items()
                           if k_.startswith("strong")),
                          key=lambda kv: kv[1]["devices"]):
        n = d["devices"]
        out.append(BenchResult(f"scaling_{mesh}", 0.0, derived={
            "devices": n,
            "flops_frac_of_1dev": round(d["flops_per_dev"]
                                        / max(sbase["flops_per_dev"], 1), 4),
            "ideal": round(1.0 / n, 4),
            "hbm_frac_of_1dev": round(d["hbm_per_dev"]
                                      / max(sbase["hbm_per_dev"], 1), 4),
            "coll_bytes_per_dev": int(d["coll_per_dev"]),
        }))
    wbase = data["weak_1x1"]
    for mesh, d in sorted(((k_, v) for k_, v in data.items()
                           if k_.startswith("weak")),
                          key=lambda kv: kv[1]["devices"]):
        n = d["devices"]
        out.append(BenchResult(f"scaling_{mesh}", 0.0, derived={
            "devices": n,
            "flops_per_dev_vs_1dev": round(
                d["flops_per_dev"] / max(wbase["flops_per_dev"], 1), 3),
            "ideal": 1.0,  # weak scaling: constant per-device work
            "hbm_per_dev_vs_1dev": round(
                d["hbm_per_dev"] / max(wbase["hbm_per_dev"], 1), 3),
            "coll_bytes_per_dev": int(d["coll_per_dev"]),
        }))
    return out
