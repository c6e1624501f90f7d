"""Rank program for the frozen-worker liveness chaos test (not pytest).

Launched by ``paddlebox_tpu.launch``: every rank joins the JAX
coordination service and drives lockstep KV-channel allgathers (the
host-planning plane a real multi-host pass rides) under a liveness
watchdog with KV heartbeats.  One rank — argv ``stall_rank`` — activates
a hang-injection fault plan through the PBOX_FAULT_PLAN env path,
freezing itself mid-gather; the whole fleet must then abort with a
DistributedStallError naming that rank instead of hanging forever.

Device collectives are deliberately absent: this jaxlib's CPU backend has
no cross-process computations, and the liveness plane is host-side by
design (the same reason the planning plane is).

argv: n_steps stall_rank site spec deadline_s
exit codes: 7 = aborted with DistributedStallError (expected),
3 = completed (the test treats that as failure), anything else = crash.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

n_steps = int(sys.argv[1])
stall_rank = int(sys.argv[2])
site, spec = sys.argv[3], sys.argv[4]
deadline_s = float(sys.argv[5])

rank = int(os.environ.get("PBOX_PROCESS_ID", "0"))
if rank == stall_rank:
    # the env-activation path: the plan is read lazily on first inject()
    os.environ["PBOX_FAULT_PLAN"] = f"{site}={spec}"

from paddlebox_tpu.parallel.mesh import initialize_distributed  # noqa: E402

initialize_distributed()  # joins the launcher's coordinator


def main() -> int:
    import numpy as np

    from paddlebox_tpu.config import LivenessConfig
    from paddlebox_tpu.parallel import watchdog as wmod
    from paddlebox_tpu.parallel.host_plane import KvChannel

    liveness = LivenessConfig(
        deadline_s=deadline_s,
        heartbeat_interval_s=deadline_s / 6,
        poll_interval_s=min(0.2, deadline_s / 10),
        hard_exit_grace_s=15.0,
    )
    wd = wmod.for_trainer(liveness, namespace="fleet")
    assert wd is not None and wd.kv is not None, "expected a KV-backed watchdog"
    wd.start()
    ch = KvChannel("fleet-work", timeout_s=120.0)
    try:
        for i in range(n_steps):
            wd.report("step")
            out = ch.allgather(np.asarray([rank * 1000 + i], np.int64))
            assert out.shape[0] == wd.world, out.shape
            time.sleep(0.05)
    except wmod.DistributedStallError as e:
        print(f"STALL-ABORT rank={rank}: {e}", flush=True)
        return 7
    finally:
        wd.close()
    print("COMPLETED-UNEXPECTEDLY", flush=True)
    return 3


if __name__ == "__main__":
    sys.exit(main())
