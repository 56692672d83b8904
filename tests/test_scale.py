"""Pod-scale rehearsal (VERDICT r4 task 4).

The 8→64 scaling story had zero execution above N=8 anywhere: the
reservation barrier had never seen 64 concurrent clients, bootstrap had
never run at pod-like world sizes, and the client poll loop had never
been observed under a re-registration storm. These tests exercise the
control plane at the north-star scale (SURVEY.md §2 reservation row,
§7.3 "Fixed-world bootstrap") with threads standing in for executors —
the protocol work (sockets, registration, barrier) is identical; only
the process boundary is faked.

Barrier formation time is printed and recorded in docs/scaling.md.
"""

import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from tensorflowonspark_tpu import reservation


def test_reservation_barrier_64_clients_with_retry_storm():
    """64 concurrent clients form the barrier; a third of them re-register
    (retried-task storm) and garbage connections probe the server mid-
    formation. Every client must see the same 64-node sorted view, with
    no double counting."""
    n = 64
    server = reservation.Server(n)
    addr = server.start()
    results = [None] * n
    errors = []
    t0 = time.monotonic()

    def client(i):
        try:
            c = reservation.Client(addr)
            meta = {"executor_id": i, "host": "127.0.0.1", "port": 20000 + i,
                    "job_name": "chief" if i == 0 else "worker",
                    "task_index": 0 if i == 0 else i - 1}
            c.register(meta)
            if i % 3 == 0:
                # retried-worker storm: same executor id registers again
                # (fresh port, as a restarted task would) — the barrier
                # must REPLACE, not double-count
                c2 = reservation.Client(addr)
                c2.register(dict(meta, port=30000 + i))
                c2.close()
            results[i] = c.await_reservations(timeout=120,
                                              poll_interval=0.05)
            c.close()
        except Exception as e:  # noqa: BLE001 - surfaces in assertion
            errors.append((i, e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()

    # garbage probes mid-formation: the server must shrug these off
    for _ in range(3):
        s = socket.create_connection(addr, timeout=5)
        s.sendall(b"\xff\xff\xff\xff not a reservation message")
        s.close()

    for t in threads:
        t.join(timeout=150)
    formation_s = time.monotonic() - t0
    server.stop()

    assert not errors, errors[:3]
    assert all(r is not None for r in results)
    for r in results:
        assert len(r) == n, "barrier opened with {} != {} nodes".format(
            len(r), n)
        ids = [m["executor_id"] for m in r]
        # the invariant everything downstream depends on: the sorted id
        # list (process_id = sorted index) is identical in every view,
        # with no double-counted re-registrations. Mutable fields (a
        # re-registered worker's port) are snapshot semantics: a client
        # that fetched before the late replacement legitimately holds
        # the older port — the stress run demonstrates exactly that.
        assert ids == list(range(n)), "dup, missing, or misordered ids"
    # the server's own final view carries every replacement
    final = {m["executor_id"]: m["port"]
             for m in server.reservations.get()}
    for i in range(n):
        want = 30000 + i if i % 3 == 0 else 20000 + i
        assert final[i] == want, (i, final[i])
    print("barrier formation, 64 clients: {:.2f}s".format(formation_s))
    assert formation_s < 60, formation_s


def test_server_side_barrier_wait_at_64():
    """The driver-side await (cluster.run's path) under the same load,
    plus stragglers: the last client registers late and the barrier must
    hold closed until then."""
    n = 64
    server = reservation.Server(n)
    addr = server.start()

    def register(i, delay=0.0):
        if delay:
            time.sleep(delay)
        c = reservation.Client(addr)
        c.register({"executor_id": i, "host": "h", "port": i,
                    "job_name": "worker", "task_index": i})
        c.close()

    for i in range(n - 1):
        threading.Thread(target=register, args=(i,), daemon=True).start()
    time.sleep(0.5)
    assert not server.reservations.done(), \
        "barrier must hold for the straggler"
    threading.Thread(target=register, args=(n - 1, 0.5), daemon=True).start()
    info = server.await_reservations(timeout=60)
    server.stop()
    assert len(info) == n
    assert [m["executor_id"] for m in info] == sorted(
        m["executor_id"] for m in info)


@pytest.mark.slow
def test_dryrun_multichip_16_devices():
    """The driver validates dryrun_multichip(8) every round; the 16-device
    shape (VERDICT r4 task 4) exercises the larger hybrid mesh
    factorizations (DCN x ICI) on this side of the driver. ~60s of XLA
    compiles on the 1-core box."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               TFOS_TPU_DISTRIBUTED="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(16); print('OK')"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_scaling_traffic_n_invariance():
    """The DP scaling model's traffic term, measured instead of assumed
    (VERDICT r4 weak #3): compile AND execute the sharded train step at
    8 and 16 virtual devices and assert XLA inserts the same all-reduce
    traffic per parameter at both — the invariance the analytic 8->64
    table rests on. The full 8/16/32/64 sweep runs via
    `scripts/scaling_model.py --sweep` (docs/scaling.md); two points
    keep the CI cost to ~1 min."""
    import json
    import subprocess
    import sys as _sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [_sys.executable, os.path.join(repo, "scripts/scaling_model.py"),
         "--sweep", "8,16"],
        capture_output=True, text=True, timeout=1500, cwd=repo)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1000:]
    rec = json.loads(out.stdout[out.stdout.index("{"):])
    assert rec["all_points_ok"] is True, rec
    assert rec["ratio_n_invariant"] is True, rec


@pytest.mark.slow
def test_tp_collective_traffic_measured_at_width():
    """The TP analog of the DP traffic test: compile AND execute the
    megatron-sharded BERT step at tp=2 and tp=4 and read the collective
    bytes XLA actually inserted (scripts/tp_scaling_model.py;
    docs/scaling.md). tp=4 also regression-covers the indivisible-dim
    fallback in tree_shardings — it was a hard device_put error before
    this harness existed."""
    import json
    import subprocess
    import sys as _sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [_sys.executable, os.path.join(repo, "scripts/tp_scaling_model.py"),
         "--sweep", "2,4"],
        capture_output=True, text=True, timeout=1500, cwd=repo)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1000:]
    rec = json.loads(out.stdout[out.stdout.index("{"):])
    assert rec["all_points_ok"] is True, rec
    for p in rec["sweep"]:
        assert p["step_executed"] and p["total_collective_bytes"] > 0, p
