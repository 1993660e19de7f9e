#!/usr/bin/env python
"""Multi-host training launcher.

Parity: tools/launch.py — the reference spawns scheduler + servers +
workers through dmlc-tracker (ssh/mpi/sge/yarn) and wires them with
DMLC_* env vars.  TPU-native translation (SURVEY §2.10): there is no
parameter server; every host runs the SAME program and joins a
jax.distributed cluster (coordinator = host 0), with collectives over
ICI/DCN doing what ps-lite push/pull did.

Launchers:
  local  — a CPU REHEARSAL: N processes on this machine, each forced
           to JAX_PLATFORMS=cpu with its own fake host devices.  It
           never uses an accelerator, even on a chip host: a chip
           belongs to one process, so N local workers cannot share one
           host's chips.  To train on the chips of one host, run ONE
           process over all of them (ShardedTrainer / Module with a
           context list); to train across hosts, use ssh.
  ssh    — one process per host from --host-file via ssh
  print  — emit the per-host command lines (for any external scheduler)

Env contract consumed by mxnet_tpu.kvstore.create('dist_*'):
  MXTPU_COORDINATOR   host:port of process 0
  MXTPU_NUM_WORKERS   total process count
  MXTPU_WORKER_RANK   this process's rank
(The reference's DMLC_PS_ROOT_URI/DMLC_NUM_WORKER/DMLC_ROLE analogs.)

IMPORTANT: worker scripts must call mx.kvstore.create('dist_*') BEFORE
creating NDArrays or touching jax — jax.distributed.initialize has to run
before the backend comes up (same rule as the reference, where the
kvstore/ps rendezvous happens at import/create time, kvstore.py:360).

Elastic mode (--elastic, docs/resilience.md "Elasticity"): the local
launcher becomes a supervise loop.  Each incarnation runs at an agreed
world size; when the workers exit EXIT_RESTART (3) after adopting a
re-mesh verdict, the launcher reads the generation ledger the
coordinator wrote (<elastic-dir>/LEDGER.json), respawns at the agreed
world size with MXTPU_ELASTIC_GENERATION stamped one higher, and keeps
going until the workers exit cleanly, fail hard, or the agreed world
would dip below --min-world.
"""
import argparse
import json
import os
import shlex
import signal
import subprocess
import sys
import time


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one telemetry correlation id for the whole pod launch, so every rank's
# events-rank*.jsonl carries the same run_id (docs/observability.md)
_POD_RUN_ID = os.environ.get("MXTPU_RUN_ID") or \
    "%s-%d" % (time.strftime("%Y%m%d%H%M%S"), os.getpid())


def build_env(rank, args):
    env = dict(os.environ)
    env["MXTPU_COORDINATOR"] = "%s:%d" % (args.coordinator, args.port)
    env["MXTPU_NUM_WORKERS"] = str(args.num_workers)
    env["MXTPU_WORKER_RANK"] = str(rank)
    env["MXTPU_RUN_ID"] = _POD_RUN_ID
    # reference-compat aliases (kvstore.py reads these too)
    env["DMLC_NUM_WORKER"] = str(args.num_workers)
    env["DMLC_ROLE"] = "worker"
    # spawned roles must find mxnet_tpu no matter where the user launched
    # from (the reference tracker syncs the workdir; we ship PYTHONPATH)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def launch_local(args, command):
    procs = []
    workdir = args.workdir or os.getcwd()
    sys.stderr.write(
        "launch.py: local launcher = CPU rehearsal: %d workers x %d fake "
        "host devices, JAX_PLATFORMS=cpu (no accelerator is used)\n"
        % (args.num_workers, args.devices_per_worker))
    for rank in range(args.num_workers):
        env = build_env(rank, args)
        # local mode is a CPU rehearsal by design: N workers on one host
        # cannot share its chips (a chip belongs to one process), so
        # each gets its own fake host devices whatever the outer env
        # says
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % args.devices_per_worker)
        procs.append(subprocess.Popen(command, env=env, cwd=workdir))

    def _kill(*_):
        for p in procs:
            p.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    # Poll instead of serially wait()ing: when any worker exits with
    # EXIT_RESTART (3, the resilience restart signal — see
    # docs/resilience.md) the siblings are torn down promptly and the
    # launcher itself exits 3, so the pod restarts bounded rather than
    # draining whatever hang/fault triggered the abort.  Other nonzero
    # codes drain, and the FIRST one is reported — never OR-merged,
    # which could fabricate 3 (workers exiting 1 and 2 OR to 3) and
    # trick a supervisor into restarting a non-restartable failure.
    import time as _time
    rc = 0
    saw_signal = False
    live = list(procs)
    while live:
        still = []
        for p in live:
            code = p.poll()
            if code is None:
                still.append(p)
            elif code == 3:
                # grace before the teardown: peers of an agreed re-mesh
                # all exit 3 on their own within moments, and a SIGTERM
                # mid-exit can tear away un-flushed telemetry (the
                # elastic adopt trail); only genuinely hung siblings
                # ride out the full window
                deadline = _time.time() + 5.0
                while _time.time() < deadline and \
                        any(q.poll() is None for q in procs):
                    _time.sleep(0.1)
                for q in procs:
                    if q.poll() is None:
                        q.terminate()
                for q in procs:
                    try:
                        q.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        q.kill()
                return 3
            else:
                rc = rc or code
                saw_signal = saw_signal or code < 0
        live = still
        if live:
            _time.sleep(0.1)
    if saw_signal and getattr(args, "elastic", False):
        # Elastic contract: a worker that died BY SIGNAL was preempted
        # or torn down by the runtime, not failed by its own code — in
        # particular, losing the jax coordinator process SIGABRTs every
        # survivor from a C++ thread (xla client.h LOG(QFATAL)) before
        # any Python orphan path can run.  Report the restart signal so
        # the supervise loop can bump the generation and respawn at the
        # surviving capacity; deliberate failures exit with positive
        # codes and still end the loop above.
        return 3
    return rc


# ----------------------------------------------------------------------
# elastic supervise loop (--elastic)
# ----------------------------------------------------------------------
# NOTE: the ledger/capacity readers are duplicated from
# mxnet_tpu/resilience/elastic.py on purpose — the launcher must stay
# importable without jax/mxnet_tpu (it is the thing that sets up the
# environment those imports need).  Format contract: LEDGER.json is one
# JSON object {"generation": int, "world_size": int, ...}; capacity is
# a bare int in <elastic-dir>/capacity (or MXTPU_ELASTIC_CAPACITY_FILE).

def _elastic_log(msg, *fmt):
    sys.stderr.write("[launch.elastic] " + (msg % fmt if fmt else msg)
                     + "\n")
    sys.stderr.flush()


def _read_ledger(path):
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


def _read_capacity(elastic_dir):
    path = os.environ.get("MXTPU_ELASTIC_CAPACITY_FILE") or \
        os.path.join(elastic_dir, "capacity")
    try:
        with open(path) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def supervise_elastic(args, command):
    """Run --launcher local under the elastic restart contract.

    Incarnation k runs at the agreed world size with
    MXTPU_ELASTIC_GENERATION=k's generation in the environment.  When
    the pod exits EXIT_RESTART (3) the loop adopts the newer verdict
    from the generation ledger if the coordinator committed one
    (normal re-mesh), else bumps the generation itself (the orphan
    path: coordinator died before publishing — the respawned pod
    re-ranks from scratch, so same-world respawn is safe locally).
    Any other exit code ends the loop and is returned as-is.
    """
    target = args.num_workers
    min_world = max(int(args.min_world), 1)
    elastic_dir = os.path.abspath(
        args.elastic_dir or os.path.join(os.getcwd(), "mxtpu_elastic"))
    os.makedirs(elastic_dir, exist_ok=True)
    ledger_path = os.path.join(elastic_dir, "LEDGER.json")
    base_port = args.port

    gen, world = 0, target
    led = _read_ledger(ledger_path)
    if led is not None:      # resuming a supervised run mid-agreement
        gen = int(led.get("generation", 0))
        world = int(led.get("world_size", target))
        _elastic_log("resuming from ledger: generation=%d world=%d",
                     gen, world)

    restarts = 0
    while True:
        cap = _read_capacity(elastic_dir)
        if cap is not None and cap < world:
            _elastic_log("capacity %d below agreed world %d; clamping",
                         cap, world)
            world = cap
        if world < min_world:
            _elastic_log("agreed world %d below --min-world %d; refusing "
                         "to spawn (waiting for capacity is the "
                         "operator's call)", world, min_world)
            return 3
        # inherited by build_env via os.environ — every rank of this
        # incarnation sees the same generation stamp
        os.environ["MXTPU_ELASTIC"] = "1"
        os.environ["MXTPU_ELASTIC_DIR"] = elastic_dir
        os.environ["MXTPU_ELASTIC_MIN_WORLD"] = str(min_world)
        os.environ["MXTPU_ELASTIC_GENERATION"] = str(gen)
        os.environ["MXTPU_ELASTIC_TARGET_WORLD"] = str(target)
        # warm elasticity: the handoff area must outlive each
        # incarnation, so it defaults under the (stable) elastic dir;
        # an explicit MXTPU_HANDOFF_DIR (e.g. a /dev/shm tmpfs for true
        # disklessness) wins
        os.environ.setdefault("MXTPU_HANDOFF_DIR",
                              os.path.join(elastic_dir, "handoff"))
        if getattr(args, "warm", False):
            os.environ["MXTPU_WARM_REMESH"] = "1"
        args.num_workers = world
        # fresh port per incarnation: the previous coordinator's socket
        # may linger in TIME_WAIT past the respawn
        args.port = base_port + (restarts % 32)
        _elastic_log("incarnation %d: generation=%d world=%d port=%d",
                     restarts, gen, world, args.port)
        rc = launch_local(args, command)
        if rc != 3:
            _elastic_log("pod exited rc=%d after %d restart(s); done",
                         rc, restarts)
            return rc
        restarts += 1
        if args.max_restarts is not None and restarts > args.max_restarts:
            _elastic_log("restart budget (%d) exhausted", args.max_restarts)
            return 3
        led = _read_ledger(ledger_path)
        if led is not None and int(led.get("generation", -1)) > gen:
            gen = int(led.get("generation"))
            world = int(led.get("world_size", world))
            _elastic_log("adopting verdict: generation=%d world=%d "
                         "reason=%s", gen, world, led.get("reason"))
        else:
            gen += 1
            _elastic_log("no newer verdict in ledger (coordinator lost?) "
                         "— bumping generation to %d, same world", gen)


def launch_ssh(args, command):
    hosts = [h.strip() for h in open(args.host_file) if h.strip()]
    if len(hosts) < args.num_workers:
        raise SystemExit("host file has %d hosts < -n %d"
                         % (len(hosts), args.num_workers))
    procs = []
    for rank in range(args.num_workers):
        env = build_env(rank, args)
        exports = " ".join("%s=%s" % (k, shlex.quote(v))
                           for k, v in env.items()
                           if k.startswith(("MXTPU_", "DMLC_", "JAX_",
                                            "XLA_", "PYTHONPATH")))
        remote = "cd %s && env %s %s" % (
            shlex.quote(args.workdir) if args.workdir else "~", exports,
            " ".join(shlex.quote(c) for c in command))
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no",
                                       hosts[rank], remote]))
    rc = 0
    for p in procs:
        code = p.wait()
        rc = rc or code              # first nonzero; OR could fabricate 3
    return rc


def launch_print(args, command):
    for rank in range(args.num_workers):
        env = build_env(rank, args)
        exports = " ".join("%s=%s" % (k, v) for k, v in sorted(env.items())
                           if k.startswith(("MXTPU_", "DMLC_")))
        print("# rank %d" % rank)
        print("env %s %s" % (exports, " ".join(command)))
    return 0


def main():
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=("local", "ssh", "print"),
                        default="local",
                        help="local = CPU rehearsal on this machine (never "
                             "an accelerator); ssh = one process per host")
    parser.add_argument("-H", "--host-file", type=str, default=None)
    parser.add_argument("--coordinator", type=str, default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9870)
    parser.add_argument("--workdir", type=str, default=None)
    parser.add_argument("--devices-per-worker", type=int, default=2,
                        help="fake devices per process for --launcher local")
    parser.add_argument("--elastic", action="store_true",
                        help="supervise loop: respawn at the ledger-agreed "
                             "world size on EXIT_RESTART (local only)")
    parser.add_argument("--min-world", type=int, default=1,
                        help="--elastic: refuse to spawn below this world "
                             "size (MXTPU_ELASTIC_MIN_WORLD)")
    parser.add_argument("--elastic-dir", type=str, default=None,
                        help="--elastic: ledger/capacity directory "
                             "(default ./mxtpu_elastic)")
    parser.add_argument("--max-restarts", type=int, default=None,
                        help="--elastic: give up after this many respawns")
    parser.add_argument("--warm", action="store_true",
                        help="--elastic: warm re-mesh — set "
                             "MXTPU_WARM_REMESH=1 so transitions resume "
                             "from host-memory hot state instead of the "
                             "checkpoint (docs/resilience.md)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        raise SystemExit("no command given")

    if args.elastic and args.launcher != "local":
        raise SystemExit("--elastic is only supported with "
                         "--launcher local")
    if args.elastic:
        rc = supervise_elastic(args, args.command)
    elif args.launcher == "local":
        rc = launch_local(args, args.command)
    elif args.launcher == "ssh":
        rc = launch_ssh(args, args.command)
    else:
        rc = launch_print(args, args.command)
    sys.exit(rc)


if __name__ == "__main__":
    main()
