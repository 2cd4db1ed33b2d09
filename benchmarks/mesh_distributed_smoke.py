"""Two-process ``jax.distributed`` smoke for the 2-D mesh scale-out path:
a CPU rehearsal.

Run with no arguments, the driver re-executes itself as two coordinated
worker processes (``--process-id 0|1``), each given two forced host
devices on the CPU backend (``JAX_PLATFORMS=cpu`` in their environment,
so on a TPU host neither child claims the chips), and checks the
multi-process story end to end as far as the CPU backend permits:

  1. ``jax.distributed.initialize`` handshake: both workers join one
     coordinator and each sees the OTHER's devices in the global world
     (4 global / 2 local) -- the topology a real multi-host TPU mesh
     starts from.
  2. Per-worker 2-D parity: each worker runs the row-sharded fused
     dispatch (``MeshSpec(rows=2)``, halo exchange and all) over its two
     local devices and asserts bitwise equality with its single-device
     run.  This is exactly the per-host slice of a multi-host rollout.
  3. Truthful degradation across the process boundary: a spec spanning
     the whole 4-device *global* world exceeds each worker's 2
     *addressable* devices, so the fleet must degrade to the bitwise
     single-device fallback AND stamp ``mesh_degraded`` -- never
     silently pretend to the global shape.

Cross-process collectives themselves are NOT exercised: XLA:CPU raises
``Multiprocess computations aren't implemented on the CPU backend``, so
a CPU CI can validate the handshake, the world assembly, and the
per-host shard math, while the collective seam exchange across hosts
needs a real TPU/GPU runner.
Exits 0 on success, 1 with the failing worker's log on any mismatch.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

COORD = "127.0.0.1:12357"
N_PROCS = 2
LOCAL_DEVICES = 2


def worker(process_id: int) -> None:
    import jax

    jax.distributed.initialize(
        coordinator_address=COORD, num_processes=N_PROCS,
        process_id=process_id,
    )
    import numpy as np

    assert len(jax.local_devices()) == LOCAL_DEVICES, jax.local_devices()
    assert jax.device_count() == N_PROCS * LOCAL_DEVICES, jax.devices()
    assert jax.process_count() == N_PROCS
    print(f"[worker {process_id}] joined: {len(jax.local_devices())} local "
          f"/ {jax.device_count()} global devices", flush=True)

    from repro.core import MeshSpec, sobel_grid
    from repro.runtime.fleet import FleetRequest, PixieFleet

    grid = sobel_grid()
    rng = np.random.default_rng(process_id)
    names = ("sobel_x", "threshold", "sobel_y", "identity")
    frames = [rng.integers(0, 256, hw).astype(np.int32)
              for hw in ((13, 17), (8, 8), (21, 9), (5, 30))]

    def run(spec):
        fleet = PixieFleet(default_grid=grid, mesh=spec, batch_tile=1)
        tickets = [fleet.submit(FleetRequest(app=n, image=f))
                   for n, f in zip(names, frames)]
        res = fleet.flush()
        return [np.asarray(res[t]) for t in tickets], fleet

    base, _ = run(MeshSpec())
    got, fleet = run(MeshSpec(rows=LOCAL_DEVICES))
    for b, g in zip(base, got):
        np.testing.assert_array_equal(b, g)
    assert not fleet.stats.mesh_degraded, fleet.stats
    print(f"[worker {process_id}] row-sharded parity over "
          f"{LOCAL_DEVICES} local devices: bitwise OK", flush=True)

    _, global_fleet = run(MeshSpec(app=N_PROCS, rows=LOCAL_DEVICES))
    assert global_fleet.stats.mesh_degraded, global_fleet.stats
    assert global_fleet.stats.mesh_granted == (1, 1)
    print(f"[worker {process_id}] global-world spec "
          f"{N_PROCS}x{LOCAL_DEVICES} degraded truthfully "
          f"(granted 1x1, stamped)", flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--process-id", type=int, default=None)
    a = p.parse_args(argv)
    if a.process_id is not None:
        worker(a.process_id)
        return 0
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={LOCAL_DEVICES}"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--process-id", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for i in range(N_PROCS)
    ]
    rc = 0
    for i, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            rc = 1
        sys.stdout.write(out.decode(errors="replace"))
        if proc.returncode != 0:
            rc = 1
    print("mesh_distributed_smoke:", "PASS" if rc == 0 else "FAIL")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
