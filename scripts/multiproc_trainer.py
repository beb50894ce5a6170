#!/usr/bin/env python
"""Deterministic multi-process trainer: the acceptance workload for the
fault-tolerant process-spanning runtime.

Launched by ``multihost.spawn_local`` / ``scripts/launch_multiproc.py``
(all world configuration arrives via the launcher environment —
``HEAT_TPU_COORDINATOR`` / ``HEAT_TPU_PROCESS_ID`` / ``HEAT_TPU_NUM_PROCESSES``
/ ``HEAT_TPU_MESH_DIR`` / ``HEAT_TPU_MESH_EPOCH``). The workload is linear
regression by full-batch gradient descent over a FIXED seeded global
dataset, rows sharded across every device of every process:

    w  <-  w - lr * X^T (X w - y) / rows

The gradient is a mean over the *global* rows, so the trajectory is
world-size invariant: a run that loses a process mid-training, restores
from the newest verifying checkpoint onto the shrunk world and replays,
must land on the same final ``w`` as an uninterrupted run (rtol 1e-5, the
kill-a-process acceptance pin). ``X^T r`` over row-sharded operands makes
XLA insert a real cross-process psum (gloo over DCN on a CPU mesh) into
the compiled step — this trainer IS the cross-process collective smoke.

Per step the worker: polls ``multihost.check_peers()`` (lease-daemon
declarations become control flow at the step boundary), publishes a
progress beacon (``multihost.note_progress`` — the launcher's chaos
injector and recovery timing read these), and commits a checkpoint through
``utils/checkpoint.py``'s cooperative manifest protocol every
``--checkpoint-every`` steps (its save/commit barriers run under the
launcher's barrier timeout).

On peer loss — a ``PeerLostError`` from the poll, a ``StallError`` from a
barrier, or a collective torn by the dying peer — the worker writes a
partial result record and exits ``multihost.REFORM_EXIT`` so the launcher
respawns the survivors into a smaller world.

Chaos hooks (deterministic, driven by the test matrix / bench):
``--die-rank R --die-at-step S`` SIGKILLs rank R from inside at step S;
``--hang-rank R --hang-at-step S`` stops beating and sleeps forever (the
zero-hang pin: survivors must surface a named error, and the launcher
reaps the hung child).

Results land as JSON at ``--out/result-epoch{E:04d}-rank{R:05d}.json``.
"""

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
jax.config.update("jax_enable_x64", True)


def _result_path(out_dir: str, epoch: int, rank: int) -> str:
    return os.path.join(out_dir, f"result-epoch{epoch:04d}-rank{rank:05d}.json")


def _write_result(out_dir: str, doc: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = _result_path(out_dir, doc["epoch"], doc["rank"])
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _comm_failure(exc: BaseException) -> bool:
    msg = f"{type(exc).__name__}: {exc}".lower()
    return any(
        key in msg
        for key in (
            "gloo", "socket", "connection", "peer", "deadline", "barrier",
            "distributed", "coordination", "unavailable", "cancelled",
        )
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--checkpoint-every", type=int, default=2)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--out", required=True, help="result JSON directory")
    ap.add_argument("--die-rank", type=int, default=-1)
    ap.add_argument("--die-at-step", type=int, default=-1)
    ap.add_argument("--hang-rank", type=int, default=-1)
    ap.add_argument("--hang-at-step", type=int, default=-1)
    args = ap.parse_args()

    from heat_tpu.core import elastic, multihost, resilience, serving
    from heat_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    serving.use_entry_point_compile_cache()
    multihost.initialize_distributed()
    rank = multihost.process_index()
    world = multihost.process_count()
    epoch = multihost.mesh_epoch()
    lost_window_s = (
        float(os.environ.get("HEAT_TPU_PEER_LOST_MS", "1000") or 1000) / 1e3
    )

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    row2 = NamedSharding(mesh, P("x", None))
    row1 = NamedSharding(mesh, P("x"))
    rep = NamedSharding(mesh, P())

    rows, dim, lr = args.rows, args.dim, args.lr
    rng = np.random.default_rng(7)  # identical on every process by design
    X_full = rng.standard_normal((rows, dim))
    y_full = rng.standard_normal((rows,))

    X = jax.make_array_from_callback((rows, dim), row2, lambda idx: X_full[idx])
    y = jax.make_array_from_callback((rows,), row1, lambda idx: y_full[idx])

    @jax.jit
    def train_step(w, X, y):
        r = X @ w - y
        g = X.T @ r / rows  # row-sharded contraction: the cross-process psum
        return jax.lax.with_sharding_constraint(w - lr * g, rep)

    start = 0
    resumed_from = None
    newest = elastic.newest_verified_step(args.ckpt_dir)
    if newest is not None:
        restored = load_checkpoint(args.ckpt_dir, {"w": np.zeros(dim)}, step=newest)
        w_np = np.asarray(restored["w"], dtype=np.float64)
        start = resumed_from = int(newest)
    else:
        w_np = np.zeros(dim)
    w = jax.make_array_from_callback((dim,), rep, lambda idx: w_np[idx])

    doc = {
        "rank": rank, "world": world, "epoch": epoch, "status": "reform",
        "resumed_from": resumed_from, "completed_steps": start,
        "t_first_step": None, "rate_steps_per_s": None, "final_w": None,
    }

    def exit_for_reform(exc: BaseException) -> "int":
        doc["error"] = f"{type(exc).__name__}: {exc}"
        _write_result(args.out, doc)
        # NOT sys.exit: atexit would run jax.distributed.shutdown(), whose
        # barrier blocks on the dead peer and then LOG(FATAL)s this survivor
        multihost.reform_exit()
        return multihost.REFORM_EXIT  # unreachable; keeps the signature honest

    t_after_first = None
    try:
        step = start
        while step < args.steps:
            multihost.check_peers()
            if rank == args.die_rank and step == args.die_at_step:
                os.kill(os.getpid(), signal.SIGKILL)
            if rank == args.hang_rank and step == args.hang_at_step:
                multihost.stop_heartbeat()  # go silent: peers must DETECT this
                time.sleep(3600)
            w = train_step(w, X, y)
            w.block_until_ready()
            step += 1
            doc["completed_steps"] = step
            multihost.note_progress(step)
            if doc["t_first_step"] is None:
                doc["t_first_step"] = t_after_first = time.time()
            if step % args.checkpoint_every == 0 and step < args.steps:
                try:
                    save_checkpoint(
                        args.ckpt_dir, {"w": np.asarray(w)}, step=step, keep=5
                    )
                except (multihost.PeerLostError, resilience.StallError):
                    raise
                except Exception as exc:  # noqa: BLE001 - ci-fault mix survivable
                    print(
                        f"[rank {rank}] checkpoint at step {step} skipped: {exc!r}",
                        file=sys.stderr,
                    )
    except (multihost.PeerLostError, resilience.StallError) as exc:
        return exit_for_reform(exc)
    except Exception as exc:  # noqa: BLE001 - a collective torn by a dying peer?
        deadline = time.time() + 2.0 * lost_window_s
        while time.time() < deadline and not multihost.lost_peers():
            time.sleep(0.05)
        if multihost.lost_peers() or _comm_failure(exc):
            return exit_for_reform(exc)
        raise

    doc["status"] = "done"
    if t_after_first is not None and step - start > 1:
        doc["rate_steps_per_s"] = round(
            (step - start - 1) / max(time.time() - t_after_first, 1e-9), 3
        )
    doc["final_w"] = np.asarray(w).tolist()
    _write_result(args.out, doc)
    multihost.stop_heartbeat()
    return 0


if __name__ == "__main__":
    sys.exit(main())
