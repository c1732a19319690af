"""Fig. 11 — near-linear scalability to 1024 GPUs.

Per-GPU throughput retention under weak scaling. DistFlow's data plane adds
a CONSTANT per-node cost (measured: the databuffer moves only per-node
volume, zero controller bytes), so the only degradation is the FSDP gradient
sync the paper itself reports (80.5% at 512, their §7.3) — our model uses
that single point as calibration and predicts the rest of the curve. The
centralized arm's retention collapses as the controller serializes the
growing global batch.

The **simulated-fleet arm** (``--fleet``, committed baseline
``results/BENCH_fleet.json``) measures the multi-host machinery itself on
CPU-simulated fleets (docs/multihost.md):

* weak scaling over 8/16/32-device ``(pod, data, model)`` fleet meshes —
  per-device throughput retention with the prompt batch scaled to the
  device count, plus the databuffer's per-host staging volume (no
  controller bytes, no full-array gathers);
* the file-plane DP gradient exchange (``fleet.GradExchange``) driven by
  one thread per host: seconds per exchange and wire bytes for the exact
  fp32 arm vs the int8 error-feedback arm (wire_bytes saved is the number
  the compressed exchange exists for);
* ``compressed_psum`` over the pod axis: quantization rel-err and wire
  ratio for the in-process collective the fleet exchange mirrors.

Its child processes are CPU-forced on purpose: the fleet arm is a CPU
simulation and reports no device speed.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np

from benchmarks import paper_scale as ps
from benchmarks.common import bench_pipeline, emit, tiny_cfg
from repro.rl import RLConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    cfg = tiny_cfg()
    rl = RLConfig(algorithm="grpo", group_size=4, max_new_tokens=16, lr=1e-5)
    dt_d, tok, pipe_d, _ = bench_pipeline(cfg, rl, centralized=False, iters=3,
                                       prompts_per_iter=4)
    emit("fig11/measured_controller_bytes", 0.0,
         f"{pipe_d.buffer.stats.bytes_through_controller}B (distflow: must be 0)")
    emit("fig11/measured_per_iter_s", dt_d * 1e6, "per-node unit at toy scale")

    base_c = None
    for gpus in (64, 128, 256, 512, 1024):
        r_d = ps.retention(gpus)
        emit(f"fig11/distflow_retention_{gpus}gpu", 0.0,
             f"{100 * r_d:.1f}% (paper: 80.5% @512 [cal], 32B arm)")
        t_c = ps.centralized_iter_s(gpus, batch_per_node=512)
        base_c = base_c or t_c
        emit(f"fig11/centralized_retention_{gpus}gpu", 0.0,
             f"{100 * base_c / t_c:.1f}% (baseline OOMs before here, Table 1)")


# ------------------------------------------------------------------ #
# simulated-fleet arm
# ------------------------------------------------------------------ #
def _fleet_point(num_hosts: int, devices_per_host: int, iters: int) -> dict:
    """One weak-scaling cell, in a subprocess with its own forced device
    count: the tiny GRPO pipeline on the global fleet mesh, prompts scaled
    to the device count (constant per-device batch)."""
    devices = num_hosts * devices_per_host
    body = textwrap.dedent(f"""
        import json, time
        import jax
        from benchmarks.common import tiny_cfg
        from repro.configs.base import DataCoordinatorConfig
        from repro.core import build_pipeline
        from repro.launch.mesh import make_fleet_mesh
        from repro.rl import RLConfig

        cfg = tiny_cfg()
        rl = RLConfig(algorithm="grpo", group_size=4, max_new_tokens=8,
                      lr=1e-5)
        mesh = make_fleet_mesh({num_hosts}, {devices_per_host})
        pipe = build_pipeline(cfg, rl, mesh=mesh,
                              prompts_per_iter={devices}, seed=0)
        pipe.run(1)  # warmup/compile
        pipe.buffer.stats.reset()
        t0 = time.perf_counter()
        pipe.run({iters})
        dt = (time.perf_counter() - t0) / {iters}
        st = pipe.buffer.stats
        print("RESULT " + json.dumps({{
            "s_per_iter": dt,
            "controller_bytes": st.bytes_through_controller,
            "max_host_inbound_bytes": st.max_host_inbound_bytes,
            "redistributions": st.redistributions,
        }}))
    """)
    out = _run_forced(body, devices)
    rec = json.loads(out.split("RESULT ", 1)[1])
    tokens = devices * 4 * (6 + 8)  # prompts * group * (prompt + response)
    rec.update({
        "hosts": num_hosts, "devices": devices,
        "tokens_per_s": tokens / rec["s_per_iter"],
        "per_device_tokens_per_s": tokens / rec["s_per_iter"] / devices,
    })
    return rec


def _run_forced(body: str, devices: int) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'src')!r})\n"
        f"sys.path.insert(0, {REPO!r})\n"
        + body
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"fleet point failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


def _bench_exchange(workdir: str, hosts: int, params: int,
                    rounds: int) -> dict:
    """Time the file-plane GradExchange, one driver thread per host, for the
    exact and int8_ef arms on the same gradient vector."""
    import jax.numpy as jnp

    from repro.configs.base import DistributedConfig
    from repro.distributed.fleet import FleetContext, GradExchange

    rng = np.random.default_rng(0)
    grads = jnp.asarray(rng.standard_normal(params).astype(np.float32))
    result = {"hosts": hosts, "params": params, "rounds": rounds}
    for mode in ("none", "int8_ef"):
        root = os.path.join(workdir, f"xchg-{mode}")
        ctxs = [FleetContext(DistributedConfig(
            num_hosts=hosts, process_id=h, coordinator=root))
            for h in range(hosts)]
        for c in ctxs:
            c.heartbeat(0)
        exs = [GradExchange(c, mode) for c in ctxs]
        outs: dict = {}

        def drive(h):
            for _ in range(rounds):
                outs[h] = exs[h](grads)[0]

        t0 = time.perf_counter()
        ts = [threading.Thread(target=drive, args=(h,)) for h in range(hosts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = (time.perf_counter() - t0) / rounds
        st = exs[0].stats
        rel_err = float(np.linalg.norm(np.asarray(outs[0]) - np.asarray(grads))
                        / np.linalg.norm(np.asarray(grads)))
        key = "exact" if mode == "none" else "int8_ef"
        result[key] = {
            "s_per_exchange": dt,
            "wire_bytes_per_exchange": st["wire_bytes"] // rounds,
            "wire_saved_bytes_per_exchange": st["wire_saved_bytes"] // rounds,
            "wire_ratio": st["wire_bytes"] / st["exact_bytes"],
            "rel_err": rel_err,
        }
    return result


def _bench_compressed_psum(devices: int, hosts: int) -> dict:
    body = textwrap.dedent(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression
        from repro.launch.mesh import make_fleet_mesh
        from repro.utils.jax_compat import shard_map, use_mesh
        mesh = make_fleet_mesh({hosts})
        x = jax.random.normal(jax.random.PRNGKey(0), ({hosts}, 64, 256))
        def body(v):
            return (jax.lax.psum(v, 'pod'),
                    compression.compressed_psum(v, 'pod'))
        with use_mesh(mesh):
            exact, approx = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P('pod', None, None),),
                out_specs=(P('pod', None, None), P('pod', None, None)),
                check_vma=False))(x)
        exact, approx = np.asarray(exact), np.asarray(approx)
        rel = float(np.linalg.norm(exact - approx) / np.linalg.norm(exact))
        ex_b, comp_b = compression.wire_bytes(np.asarray(x[0], np.float32))
        print("RESULT " + json.dumps({{
            "devices": {devices}, "hosts": {hosts}, "rel_err": rel,
            "wire_ratio": comp_b / ex_b,
        }}))
    """)
    out = _run_forced(body, devices)
    return json.loads(out.split("RESULT ", 1)[1])


def fleet(iters: int = 2, workdir: str = "/tmp/bench_fleet") -> dict:
    os.makedirs(workdir, exist_ok=True)
    points = []
    for hosts, dph in ((2, 4), (4, 4), (8, 4)):
        points.append(_fleet_point(hosts, dph, iters))
        p = points[-1]
        emit(f"fig11/fleet_{p['devices']}dev_s_per_iter", p["s_per_iter"] * 1e6,
             f"hosts={hosts} per_device_tps={p['per_device_tokens_per_s']:.0f} "
             f"controller_bytes={p['controller_bytes']}")
    base = points[0]["per_device_tokens_per_s"]
    for p in points:
        p["retention"] = p["per_device_tokens_per_s"] / base
    xchg = _bench_exchange(workdir, hosts=4, params=1_000_000, rounds=2)
    emit("fig11/fleet_exchange_exact_s", xchg["exact"]["s_per_exchange"] * 1e6,
         f"wire={xchg['exact']['wire_bytes_per_exchange']}B")
    emit("fig11/fleet_exchange_int8_s",
         xchg["int8_ef"]["s_per_exchange"] * 1e6,
         f"wire={xchg['int8_ef']['wire_bytes_per_exchange']}B "
         f"saved={xchg['int8_ef']['wire_saved_bytes_per_exchange']}B "
         f"rel_err={xchg['int8_ef']['rel_err']:.2e}")
    cpsum = _bench_compressed_psum(devices=32, hosts=8)
    emit("fig11/fleet_compressed_psum", 0.0,
         f"rel_err={cpsum['rel_err']:.2e} wire_ratio={cpsum['wire_ratio']:.3f}")
    return {"weak_scaling": points, "grad_exchange": xchg,
            "compressed_psum": cpsum}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet", action="store_true",
                    help="run the simulated-fleet arm instead of the "
                    "projection table")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--json", type=str, default=None,
                    help="write the BENCH_fleet.json baseline here")
    args = ap.parse_args()
    if not args.fleet:
        main()
    else:
        result = fleet(iters=args.iters)
        if args.json:
            os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
            with open(args.json, "w") as f:
                json.dump(result, f, indent=2)
            print(f"wrote {args.json}")
        print(json.dumps(result, indent=2))
