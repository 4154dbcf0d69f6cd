"""Process-fault-domain smoke: the supervised multi-process serving
fleet and the coordinated multi-host elastic resume, end to end.

Two scenarios, one per plane:

- **process fleet**: a 3-replica ``ProcessReplicaSet`` — every replica
  a supervised OS child process serving a full ``ServingEngine``
  behind a unix-socket front door, sharing one on-disk AOT artifact
  tier — under 6x40 threaded load has replica 1's PROCESS SIGKILLed
  at request 60 (``FaultInjector.kill_replica_proc``). The fleet must
  serve EVERY request (failover absorbs the process death), the
  supervisor must respawn exactly one worker process, the respawned
  process must serve real traffic with 0 post-warmup compiles (its
  re-registration prewarms from the shared disk AOT tier), and fleet
  p99 is reported.

- **2-process elastic**: two coordinator-joined gloo CPU processes
  (2 virtual devices each) run the same checkpoint-free
  DistGridSearchCV on one elastic mesh. Process 1 is SIGKILLed
  mid-search (dispatch ordinal 3); process 0's round 2 classifies
  PREEMPTED, and instead of failing loud to a checkpoint restart it
  runs the EPOCH AGREEMENT (jax.distributed KV store): publishes its
  gathered-task prefix, declares the silent peer lost, agrees
  (epoch, prefix, survivor roster), shrinks the mesh to its own
  devices, and RESUMES from the agreed prefix. Gates: cv_results_
  parity 0.0 (bitwise) vs an un-preempted single-process run,
  salvaged tasks >= 50%, exactly 1 shrink and 1 epoch agreement, and
  the surviving process exits 0.

Exit code 0 = pass. Usage:

    python build_tools/procfleet_smoke.py [--fleet-only|--elastic-only]
        [--p99-ms 10000] [--salvage-frac 0.5]
"""

import json
import os
import socket
import subprocess
import sys
import threading

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: router request ordinal replica 1's process is SIGKILLed at
KILL_AT = 60
FLEET_THREADS = 6
REQS_PER_THREAD = 40
FLEET_REPLICAS = 3

#: elastic leg geometry: 8 candidates x 4 folds = 32 tasks in 4 rounds
#: of 8; BOTH processes fault at dispatch ordinal 2 — the peer
#: SIGKILLs itself (the preemption), the survivor's round classifies
#: PREEMPTED — with rounds 0-1 (16 tasks, 50%) already gathered
#: through completed collectives on both sides. SKDIST_SYNC_ROUNDS
#: pins that geometry: every gathered round crossed its collective
#: BEFORE the fault, so the salvaged prefix is exactly the rounds the
#: roster agrees on (under pipelining the in-flight rounds are
#: dropped by the multi-process no-drain salvage instead)
ELASTIC_PREEMPT_AT = 2
ELASTIC_KILL_AT = 2
ELASTIC_ROUNDS = 4
ELASTIC_LOCAL_DEVICES = 2


def _parent_env():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    sys.path.insert(0, REPO)


def _data():
    import numpy as np
    from sklearn.datasets import make_classification

    X, y = make_classification(
        n_samples=360, n_features=12, n_informative=8, random_state=7,
    )
    return X.astype(np.float32), y


# ---------------------------------------------------------------------------
# scenario 1: supervised process fleet (SIGKILL a replica process)
# ---------------------------------------------------------------------------

def scenario_process_fleet(failures, p99_budget_ms):
    import tempfile
    import time

    import numpy as np

    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import faults
    from skdist_tpu.serve import ProcessReplicaSet
    from skdist_tpu.testing.faultinject import FaultInjector

    X, y = _data()
    model = LogisticRegression(max_iter=30, engine="xla").fit(X, y)
    faults.reset_stats()
    artifact_dir = tempfile.mkdtemp(prefix="skpf-aot-")
    errors = []
    ok = [0]
    lock = threading.Lock()
    with ProcessReplicaSet(
        n_replicas=FLEET_REPLICAS,
        artifact_dir=artifact_dir,
        engine_kwargs={"max_batch_rows": 64, "max_delay_ms": 1.0},
        heartbeat_interval_s=0.25,
    ) as fleet:
        fleet.rollout("clf", model, methods=("predict",))

        def worker(tid):
            rng = np.random.RandomState(tid)
            for _ in range(REQS_PER_THREAD):
                x = rng.normal(size=(3, X.shape[1])).astype(np.float32)
                try:
                    out = fleet.predict(x, model="clf", timeout_s=30.0)
                    assert out.shape[0] == 3
                    with lock:
                        ok[0] += 1
                except Exception as exc:  # noqa: BLE001
                    with lock:
                        errors.append(repr(exc))

        inj = FaultInjector().kill_replica_proc(1, at_request=KILL_AT)
        with inj:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(FLEET_THREADS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        # let the supervisor finish a pending respawn, then push a few
        # requests so the respawned process provably serves
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if fleet.replica(1).alive:
                break
            time.sleep(0.2)
        post = 0
        for _ in range(24):
            out = fleet.predict(X[:4], model="clf", timeout_s=30.0)
            post += int(out.shape[0] == 4)
        snap = faults.snapshot()
        fleet.harvest_now()  # pull every worker's telemetry frame NOW
        st = fleet.stats()

    total = FLEET_THREADS * REQS_PER_THREAD
    if (KILL_AT, "kill_replica_proc:1") not in inj.fired:
        failures.append("process fleet: the kill never fired")
    if errors or ok[0] != total:
        failures.append(
            f"process fleet: {len(errors)} failed requests of {total} "
            f"(first: {errors[:1]})"
        )
    if post != 24:
        failures.append(
            f"process fleet: only {post}/24 post-respawn requests served"
        )
    if snap["replica_proc_restarts"] != 1:
        failures.append(
            f"process fleet: {snap['replica_proc_restarts']} supervised "
            "respawns, want exactly 1"
        )
    rep1 = st["replicas"][1]
    if not (rep1["alive"] and rep1["generation"] >= 2):
        failures.append(
            f"process fleet: replica 1 alive={rep1['alive']} "
            f"generation={rep1['generation']} after the process kill"
        )
    served_respawned = (rep1["engine"] or {}).get("completed", 0)
    if served_respawned <= 0:
        failures.append(
            "process fleet: the respawned process served nothing"
        )
    # the 0-compile gate reads the HARVESTED scoped-miss deltas (the
    # supervisor-merged telemetry, PR 15) — not a stats field each
    # worker computed about itself inside the same frame it serves
    harvest = st["harvest"]["replicas"]
    compiles = [harvest[i]["compiles_after_warmup"]
                for i in sorted(harvest) if not harvest[i]["stale"]]
    if len(compiles) != FLEET_REPLICAS:
        failures.append(
            f"process fleet: only {len(compiles)}/{FLEET_REPLICAS} "
            f"replicas harvested fresh telemetry ({harvest})"
        )
    if any(c != 0 for c in compiles):
        failures.append(
            f"process fleet: harvested post-warmup compiles {compiles} "
            "!= 0 (the respawned process must prewarm from the shared "
            "disk AOT tier)"
        )
    p99 = max((r["engine"]["p99_ms"] or 0.0)
              for r in st["replicas"] if r["engine"])
    if p99 > p99_budget_ms:
        failures.append(
            f"process fleet: p99 {p99:.1f} ms > {p99_budget_ms} ms"
        )
    import shutil

    shutil.rmtree(artifact_dir, ignore_errors=True)
    return {
        "requests": total, "failed": len(errors),
        "post_respawn_served": post,
        "failovers": snap["replica_failovers"],
        "heartbeat_misses": snap["heartbeat_misses"],
        "proc_restarts": snap["replica_proc_restarts"],
        "respawned_replica_completed": served_respawned,
        "post_warmup_compiles": compiles,
        "p99_ms": p99,
    }


# ---------------------------------------------------------------------------
# scenario 2: 2-process gloo elastic resume via epoch agreement
# ---------------------------------------------------------------------------

def elastic_child(pid, port):
    import faulthandler
    import signal as _signal

    # a hung child dumps its stacks on SIGUSR1 — the smoke's driver
    # (and a debugging human) can see WHERE a collective wedged
    faulthandler.register(_signal.SIGUSR1)
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ELASTIC_LOCAL_DEVICES}"
    )
    os.environ["SKDIST_COMPACTION"] = "0"  # pin classic round loop
    os.environ["SKDIST_SYNC_ROUNDS"] = "1"  # symmetric salvage geometry
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    import numpy as np

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend, faults
    from skdist_tpu.parallel.mesh import (
        initialize_cluster, multihost_task_mesh,
    )
    from skdist_tpu.testing.faultinject import FaultInjector

    print(f"CHILD {pid}: joining cluster", flush=True)
    # generous heartbeat tolerance: on an elastic fleet the EPOCH
    # AGREEMENT is the membership authority — the coordination
    # service's default fail-fast would SIGABRT the survivor ~100s
    # after the peer dies, defeating the resume it just performed
    initialize_cluster(
        coordinator_address=f"localhost:{port}", num_processes=2,
        process_id=pid,
        heartbeat_timeout_seconds=10_000,
    )
    print(f"CHILD {pid}: cluster up, {len(jax.devices())} devices",
          flush=True)
    mesh = multihost_task_mesh(data_axis_size=1)
    backend = TPUBackend(mesh=mesh, elastic={"agree_timeout_s": 8.0})
    X, y = _data()
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=20, engine="xla"),
        {"C": list(np.logspace(-2, 2, 8))}, cv=4,
        partitions=ELASTIC_ROUNDS, backend=backend,
    )
    if pid == 0:
        inj = FaultInjector().at_round(ELASTIC_PREEMPT_AT, kind="preempt")
    else:
        inj = FaultInjector().at_round(ELASTIC_KILL_AT, kind="kill")
    import warnings

    print(f"CHILD {pid}: fitting", flush=True)
    with inj, warnings.catch_warnings():
        warnings.simplefilter("ignore")
        gs.fit(X, y)
    print(f"CHILD {pid}: fit done", flush=True)
    # only the survivor reaches here
    snap = faults.snapshot()
    mgr = backend.elastic
    print("SCORES", pid, list(
        np.round(gs.cv_results_["mean_test_score"], 6)
    ), flush=True)
    print("ELASTIC", pid, json.dumps({
        "epoch_agreements": snap["elastic_epoch_agreements"],
        "shrinks": snap["elastic_shrinks"],
        "salvaged": snap["elastic_tasks_salvaged"],
        "agreement_events": [
            e for e in mgr.events if e["kind"] == "epoch_agreement"
        ],
        "final_devices": len(backend.devices),
    }), flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: jax's atexit distributed shutdown
    # waits at a cluster shutdown BARRIER that the dead peer can never
    # join — the work this smoke gates is already done and printed
    os._exit(0)


def elastic_ref():
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ELASTIC_LOCAL_DEVICES}"
    )
    os.environ["SKDIST_COMPACTION"] = "0"
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    import numpy as np

    from skdist_tpu.distribute.search import DistGridSearchCV
    from skdist_tpu.models import LogisticRegression
    from skdist_tpu.parallel import TPUBackend

    X, y = _data()
    gs = DistGridSearchCV(
        LogisticRegression(max_iter=20, engine="xla"),
        {"C": list(np.logspace(-2, 2, 8))}, cv=4,
        partitions=ELASTIC_ROUNDS, backend=TPUBackend(),
    ).fit(X, y)
    print("SCORES ref", list(
        np.round(gs.cv_results_["mean_test_score"], 6)
    ), flush=True)


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def scenario_elastic(failures, salvage_frac):
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # children pin their own device count
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--elastic-child", str(i), "--port", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
        )
        for i in range(2)
    ]
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out = "(timeout)"
        outs.append(out)
        print(f"--- elastic child {i} rc={p.returncode}")
        print(out[-2500:])
    ref = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--elastic-ref"],
        capture_output=True, text=True, timeout=420, env=env,
    )
    print("---", ref.stdout.strip()[-300:])

    report = {}
    # the KILLED process must die by signal, the SURVIVOR must exit 0
    if procs[0].returncode != 0:
        failures.append(
            f"elastic: survivor (process 0) exited rc="
            f"{procs[0].returncode} — it failed loud instead of "
            "resuming via epoch agreement"
        )
    if procs[1].returncode == 0:
        failures.append("elastic: process 1 exited 0 — the kill never hit")
    surv_scores = [ln for ln in outs[0].splitlines()
                   if ln.startswith("SCORES 0")]
    ref_scores = [ln for ln in ref.stdout.splitlines()
                  if ln.startswith("SCORES ref")]
    if not surv_scores or not ref_scores:
        failures.append("elastic: missing score lines")
        return report
    v_surv = surv_scores[0].split("[", 1)[1]
    v_ref = ref_scores[0].split("[", 1)[1]
    report["cv_parity_bitwise"] = v_surv == v_ref
    if v_surv != v_ref:
        failures.append(
            f"elastic: survivor cv scores != un-preempted reference "
            f"({v_surv} vs {v_ref})"
        )
    stat_lines = [ln for ln in outs[0].splitlines()
                  if ln.startswith("ELASTIC 0 ")]
    if not stat_lines:
        failures.append("elastic: missing survivor stats line")
        return report
    stats = json.loads(stat_lines[0].split(" ", 2)[2])
    report.update(stats)
    n_tasks = 8 * 4
    if stats["epoch_agreements"] != 1:
        failures.append(
            f"elastic: {stats['epoch_agreements']} epoch agreements, "
            "want exactly 1"
        )
    if stats["shrinks"] != 1:
        failures.append(
            f"elastic: {stats['shrinks']} shrinks, want exactly 1"
        )
    if stats["salvaged"] < salvage_frac * n_tasks:
        failures.append(
            f"elastic: salvaged {stats['salvaged']}/{n_tasks} tasks "
            f"(< {salvage_frac:.0%}) across the coordinated resume"
        )
    ev = stats["agreement_events"]
    if not (ev and ev[0]["survivors"] == [0] and ev[0]["lost"] == [1]):
        failures.append(
            f"elastic: agreement roster wrong: {ev}"
        )
    return report


def main(argv):
    p99_budget_ms = 10000.0
    salvage_frac = 0.5
    if "--p99-ms" in argv:
        p99_budget_ms = float(argv[argv.index("--p99-ms") + 1])
    if "--salvage-frac" in argv:
        salvage_frac = float(argv[argv.index("--salvage-frac") + 1])
    _parent_env()
    failures = []
    report = {}
    if "--elastic-only" not in argv:
        report["process_fleet"] = scenario_process_fleet(
            failures, p99_budget_ms
        )
    if "--fleet-only" not in argv:
        report["elastic_2proc"] = scenario_elastic(failures, salvage_frac)
    print(json.dumps(report, indent=1))
    print("REPORT " + json.dumps(report))  # one-line, test-parseable
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))
    msg = "PASS:"
    if "process_fleet" in report:
        pf = report["process_fleet"]
        msg += (
            f" fleet served {pf['requests']}/{pf['requests']} with a "
            f"replica PROCESS SIGKILLed mid-load ({pf['proc_restarts']} "
            f"supervised respawn, {pf['respawned_replica_completed']} "
            "requests on the respawned process, "
            f"{pf['post_warmup_compiles']} compiles, "
            f"p99 {pf['p99_ms']:.1f} ms);"
        )
    if "elastic_2proc" in report:
        el = report["elastic_2proc"]
        msg += (
            f" 2-proc gloo mesh survived participant loss via epoch "
            f"agreement (bitwise cv parity, {el['salvaged']}/32 tasks "
            f"salvaged, {el['shrinks']} shrink)"
        )
    print(msg)


if __name__ == "__main__":
    if "--elastic-child" in sys.argv:
        elastic_child(
            int(sys.argv[sys.argv.index("--elastic-child") + 1]),
            int(sys.argv[sys.argv.index("--port") + 1]),
        )
    elif "--elastic-ref" in sys.argv:
        elastic_ref()
    else:
        main(sys.argv[1:])
