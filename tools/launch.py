#!/usr/bin/env python
"""Distributed job launcher (parity: reference tools/launch.py, the
dmlc_tracker ssh/local launcher — SURVEY.md §2.2).

Local mode (the reference nightly-test pattern, tests/nightly/test_all.sh:37:
n workers + s servers + scheduler all on localhost):

    python tools/launch.py -n 2 -s 2 python my_dist_script.py

SSH mode launches the same role set across hosts from a hostfile:

    python tools/launch.py -n 4 -s 4 -H hosts --launcher ssh python train.py

MPI mode delegates process placement to mpirun (parity: reference
tools/launch.py --launcher mpi -> dmlc_tracker/mpi.py): the scheduler
runs locally, then one mpirun per role set carries the cluster env via
OpenMPI -x (or MPICH -genv with --mpi-flavor mpich):

    python tools/launch.py -n 4 -s 2 -H hosts --launcher mpi python train.py

SGE mode submits one array job per role set via qsub (parity: reference
dmlc_tracker/sge.py); the scheduler stays on the launch host and the
launcher exits when it does (all workers deregistered):

    python tools/launch.py -n 8 -s 4 --launcher sge -q gpu.q python train.py

Local SPMD mode (docs/distributed.md) brings up a MULTI-PROCESS
jax.distributed mesh on this host: every worker gets the coordinator
address (MXTPU_COORDINATOR) plus its rank (MXTPU_PROCESS_ID), so
`parallel.multihost.initialize()` joins them into ONE global device
mesh — and the parameter-server control plane (scheduler + servers) is
launched alongside, so reference-style `dist_sync` kvstore scripts run
unmodified in the same processes (-s 0 skips the PS roles for
pure-SPMD jobs):

    python tools/launch.py --local-spmd -n 2 --local-devices 2 \
        python train.py

Serve-replica mode (docs/serving.md "Multi-replica tier") launches a
serving FLEET: N copies of the command, each one replica process that
builds its tenants and calls `mxnet_tpu.router.ReplicaAgent(...).
serve_forever()` on its own exported MXTPU_ROUTER_PORT.  The full
address list is exported to every replica AND printed as one
`MXTPU_ROUTER_REPLICAS=...` line on stdout, so the operator's Router
can connect:

    python tools/launch.py --serve-replicas 4 python serve_my_model.py

One process per chip.  A TPU chip belongs to one process at a time, and
this launcher never imports JAX, so it never holds one.  Scheduler and
server roles compute on the host and are started with JAX_PLATFORMS=cpu.
On a host with TPU chips (counted from the device nodes, not through
JAX) every --serve-replicas child is given exactly one chip
(TPU_VISIBLE_CHIPS and the single-process bounds libtpu reads), and a
fleet larger than the host's chips is refused before anything starts;
--local-spmd ranks are CPU processes when --local-devices is given, and
more than one rank sharing the chips is refused — one process drives
every chip of a host (Module(context=[mx.tpu(i) ...])).
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


# servers/scheduler block inside this import-and-serve bootstrap
_SERVER_BOOTSTRAP = "import mxnet_tpu.kvstore_server as s; s.init_server_module()"


def _role_env(role, env):
    """Finish one role's environment in place; returns it.  Scheduler
    and server roles run the optimizer on host arrays and must never
    take a chip away from the workers: they get JAX_PLATFORMS=cpu."""
    env["DMLC_ROLE"] = role
    if role != "worker":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _host_chips():
    """Ids of the TPU chips this launcher may hand out, found WITHOUT
    touching JAX (a parent that initialises the TPU client holds the
    chips its children need): the accel / vfio device nodes libtpu
    itself enumerates, narrowed by an operator-set TPU_VISIBLE_CHIPS.
    Empty on a host without chips or for a job pinned to the CPU."""
    import glob

    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return []
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return [c.strip() for c in visible.split(",") if c.strip()]
    nodes = (glob.glob("/dev/accel[0-9]*")
             or glob.glob("/dev/vfio/[0-9]*"))
    return [str(i) for i in range(len(nodes))]


def _one_chip_envs(n, parser, what):
    """Per-child environment additions binding each of `n` children to
    exactly one chip — the variables libtpu reads to run as a
    single-chip process beside its siblings.  Empty dicts on a host
    without chips; more children than chips is refused up front, before
    anything starts (a chip belongs to one process at a time)."""
    chips = _host_chips()
    if not chips:
        return [{} for _ in range(n)]
    if n > len(chips):
        parser.error(
            "%s needs one TPU chip per process but this host offers %d "
            "(%s): a chip belongs to one process at a time"
            % (what, len(chips), ",".join(chips)))
    return [{
        "TPU_VISIBLE_CHIPS": chips[i],
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # each single-chip runtime needs its own controller port
        "TPU_MESH_CONTROLLER_ADDRESS": "localhost:%d" % (8476 + i),
        "TPU_MESH_CONTROLLER_PORT": str(8476 + i),
    } for i in range(n)]


def _local_spmd_env(args, parser):
    """Platform half of a --local-spmd rank's environment: ranks given
    --local-devices K are CPU processes by definition (K forced host
    devices each); without it, more than one rank on a host with TPU
    chips is refused — the first would take every chip."""
    if args.local_devices > 0:
        return {"MXTPU_LOCAL_DEVICES": str(args.local_devices),
                "JAX_PLATFORMS": "cpu"}
    if args.num_workers > 1 and _host_chips():
        parser.error(
            "--local-spmd -n %d on a host with TPU chips: the first rank "
            "would take every chip and the others fail.  One process "
            "drives all chips of a host (Module(context=[mx.tpu(i) "
            "...])); --local-spmd joins CPU processes (give "
            "--local-devices K) or one process per host"
            % args.num_workers)
    return {}


def _routable_ip():
    """The launch host's outbound IP (UDP-connect trick) — NOT
    gethostbyname(gethostname()), which maps to loopback on hosts whose
    /etc/hosts pins the hostname to 127.0.1.1; remote ranks must be able
    to reach the scheduler at this address."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def _spawn_local_scheduler(base_env):
    """Run the scheduler on the launch host at a routable address (the
    pattern shared by the mpi and sge launchers)."""
    base_env["DMLC_PS_ROOT_URI"] = _routable_ip()
    env = dict(os.environ)
    env.update(base_env)
    return subprocess.Popen([sys.executable, "-c", _SERVER_BOOTSTRAP],
                            env=_role_env("scheduler", env))


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# a worker that checkpointed at an epoch boundary and wants its full-width
# slots back exits with this code (ckpt/elastic.py YIELD_EXIT_CODE — the
# two constants must stay in lockstep)
_ELASTIC_YIELD_RC = 3


def _elastic_log(msg):
    print("[elastic] %s" % msg, file=sys.stderr, flush=True)


def _watch_generation(workers, poll=0.2):
    """Block until the generation resolves: every worker exited (returns
    the list of return codes), or SOME worker died while others still
    run (reap the survivors — they may be wedged in a collective with
    the dead peer — and return the codes with survivors marked None →
    killed)."""
    import time as _time

    while True:
        codes = [p.poll() for p in workers]
        done = [c for c in codes if c is not None]
        if len(done) == len(workers):
            return codes
        if any(c is not None and c not in (0, _ELASTIC_YIELD_RC)
               for c in codes):
            # a mid-run death: give the rest a short grace (a clean
            # near-simultaneous exit wave), then reap
            deadline = _time.time() + 2.0
            while _time.time() < deadline:
                codes = [p.poll() for p in workers]
                if all(c is not None for c in codes):
                    return codes
                _time.sleep(poll)
            for p in workers:
                if p.poll() is None:
                    p.terminate()
            deadline = _time.time() + 5.0
            while _time.time() < deadline:
                if all(p.poll() is not None for p in workers):
                    break
                _time.sleep(poll)
            for p in workers:
                if p.poll() is None:
                    p.kill()
                    p.wait()
            return [p.poll() for p in workers]
        _time.sleep(poll)


def _run_elastic(args, repo_root, platform_env):
    """Elastic supervisor (docs/checkpoint.md "Elastic workflow"): run
    the SPMD job as a sequence of GENERATIONS.  Each generation is a
    fresh set of worker processes on a fresh coordinator; when a rank
    dies mid-run the survivors are reaped (membership change, not
    in-place repair) and the next generation launches at N-1 with
    ``MXTPU_CKPT_RESUME`` pointing at the checkpoint directory, so it
    resumes from the last committed manifest and replays the identical
    global batch sequence.  With --elastic-regrow the shrunken
    generation is asked (regrow.request sentinel) to yield at its next
    epoch boundary — exit code _ELASTIC_YIELD_RC — and relaunches at
    full width without burning a restart."""
    ckpt_dir = os.environ.get("MXTPU_CKPT_DIR")
    if not ckpt_dir:
        _elastic_log("error: --elastic requires MXTPU_CKPT_DIR "
                     "(the checkpoint directory is the recovery medium)")
        return 2
    os.makedirs(ckpt_dir, exist_ok=True)
    full_n = args.num_workers
    n = full_n
    restarts = 0
    generation = 0
    while True:
        coord = "127.0.0.1:%d" % _free_port()
        _elastic_log("generation %d: %d worker(s), coordinator %s"
                     % (generation, n, coord))
        workers = []
        for i in range(n):
            env = dict(os.environ)
            env["MXTPU_COORDINATOR"] = coord
            env["DMLC_NUM_WORKER"] = str(n)
            env["MXTPU_PROCESS_ID"] = str(i)
            env["DMLC_WORKER_ID"] = str(i)
            env["MXTPU_ELASTIC_GENERATION"] = str(generation)
            # lenient resume: an empty dir (generation 0) starts fresh
            env["MXTPU_CKPT_RESUME"] = ckpt_dir
            env.update(platform_env)
            env["PYTHONPATH"] = (repo_root + os.pathsep
                                 + os.environ.get("PYTHONPATH", ""))
            workers.append(subprocess.Popen(args.command, env=env))
        codes = _watch_generation(workers)
        dead = [r for r, c in enumerate(codes)
                if c not in (0, _ELASTIC_YIELD_RC)]
        if not dead:
            if any(c == _ELASTIC_YIELD_RC for c in codes):
                # the shrunken generation yielded at an epoch boundary:
                # relaunch at full width (budget-free — nothing failed)
                _elastic_log("generation %d yielded for regrow; "
                             "relaunching at %d worker(s)"
                             % (generation, full_n))
                # consume the sentinel: the full-width generation must
                # not see a stale request and yield again immediately
                try:
                    os.unlink(os.path.join(ckpt_dir, "regrow.request"))
                except OSError:
                    pass
                n = full_n
                generation += 1
                continue
            _elastic_log("generation %d finished cleanly" % generation)
            return 0
        if restarts >= args.elastic_max_restarts:
            _elastic_log(
                "generation %d lost rank(s) %s but the restart budget "
                "(%d) is spent; giving up" % (generation, dead, restarts))
            return 1
        restarts += 1
        n = max(args.elastic_min_workers, n - len(dead))
        _elastic_log("generation %d lost rank(s) %s (codes %s); "
                     "shrinking to %d worker(s) and resuming from '%s' "
                     "(restart %d/%d)"
                     % (generation, dead, codes, n, ckpt_dir, restarts,
                        args.elastic_max_restarts))
        if args.elastic_regrow and n < full_n:
            # ask the shrunken generation to hand its slots back at the
            # next epoch boundary (ckpt/elastic.py reads the sentinel)
            from_path = os.path.join(ckpt_dir, "regrow.request")
            with open(from_path, "w") as f:
                f.write("regrow\n")
        generation += 1


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", type=int, default=None)
    parser.add_argument("-s", "--num-servers", type=int, default=None)
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--launcher", choices=["local", "ssh", "mpi", "sge",
                                               "yarn"],
                        default="local")
    parser.add_argument("-q", "--sge-queue", default=None,
                        help="(sge) queue name passed to qsub -q")
    parser.add_argument("--sync-dst-dir", type=str, default=None,
                        help="(ssh) rsync working dir to this path on each host")
    parser.add_argument("--mpi-flavor", choices=["openmpi", "mpich"],
                        default="openmpi",
                        help="(mpi) env-forwarding syntax: -x vs -genv")
    parser.add_argument("--local-spmd", action="store_true",
                        help="launch -n worker processes joined into ONE "
                             "jax.distributed global device mesh on this "
                             "host (exports MXTPU_COORDINATOR + "
                             "MXTPU_PROCESS_ID per rank; workers call "
                             "parallel.multihost.initialize()).  The PS "
                             "scheduler/servers launch alongside so "
                             "dist_sync kvstore scripts run unmodified; "
                             "-s 0 skips them.  See docs/distributed.md")
    parser.add_argument("--local-devices", type=int, default=0,
                        help="(--local-spmd) per-process CPU device count "
                             "(exported as MXTPU_LOCAL_DEVICES; "
                             "multihost.initialize applies it via "
                             "XLA_FLAGS); 0 = platform default")
    parser.add_argument("--obs", action="store_true",
                        help="(--local-spmd) arm the distributed "
                             "observability plane: exports a free "
                             "MXTPU_OBS_PORT so rank 0 aggregates "
                             "cross-rank telemetry (cluster JSONL via "
                             "MXTPU_OBS_CLUSTER_FILE, rendered by "
                             "parse_log.py --cluster) and every rank "
                             "measures its clock offset for trace "
                             "stitching (tools/obs_stitch.py); combine "
                             "with MXTPU_OBS_STALL_SECONDS for the "
                             "collective stall watchdog.  See "
                             "docs/observability.md")
    parser.add_argument("--elastic", action="store_true",
                        help="(--local-spmd) supervise the SPMD job "
                             "elastically (docs/checkpoint.md): on a "
                             "mid-run rank death, reap the survivors and "
                             "relaunch at N-1 resuming from the last "
                             "committed checkpoint in MXTPU_CKPT_DIR "
                             "(exported as MXTPU_CKPT_RESUME); requires "
                             "MXTPU_CKPT_DIR and -s 0 (pure SPMD, no "
                             "parameter servers)")
    parser.add_argument("--elastic-max-restarts", type=int, default=2,
                        help="(--elastic) how many mid-run rank deaths "
                             "to survive before giving up")
    parser.add_argument("--elastic-min-workers", type=int, default=1,
                        help="(--elastic) never shrink below this many "
                             "workers")
    parser.add_argument("--elastic-regrow", action="store_true",
                        help="(--elastic) after a shrink, ask the "
                             "running generation to yield at its next "
                             "epoch boundary and relaunch at full width")
    parser.add_argument("--serve-replicas", type=int, default=0,
                        help="launch a serving fleet instead of a PS/SPMD "
                             "job: N copies of the command, each one "
                             "router.ReplicaAgent process with its own "
                             "exported MXTPU_ROUTER_PORT + "
                             "MXTPU_REPLICA_ID (+ MXTPU_PROCESS_ID=i+1 "
                             "so file sinks suffix .r<i+1> for trace "
                             "stitching); the full address list is "
                             "exported to every replica and printed as "
                             "one MXTPU_ROUTER_REPLICAS= line for the "
                             "Router to connect to (docs/serving.md "
                             "'Multi-replica tier')")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    if args.serve_replicas:
        if args.launcher != "local" or args.local_spmd:
            parser.error("--serve-replicas implies the local launcher")
        chip_envs = _one_chip_envs(
            args.serve_replicas, parser,
            "--serve-replicas %d" % args.serve_replicas)
        ports = [_free_port() for _ in range(args.serve_replicas)]
        addrs = ",".join("127.0.0.1:%d" % p for p in ports)
        # the line the operator's router reads back; flushed BEFORE the
        # fleet spawns so a wrapper can start connecting while replicas
        # warm up
        print("MXTPU_ROUTER_REPLICAS=%s" % addrs, flush=True)
        procs = []

        # a terminated launcher must take its fleet down with it: the
        # finally below never runs on SIGTERM (default handling exits
        # without unwinding), which would orphan N serve_forever()
        # processes holding ports and CPU
        import signal as _signal

        def _reap(signum, _frame):
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            sys.exit(128 + signum)

        _signal.signal(_signal.SIGTERM, _reap)
        _signal.signal(_signal.SIGINT, _reap)
        for i, port in enumerate(ports):
            env = dict(os.environ)
            env["MXTPU_REPLICA_ID"] = str(i)
            env["MXTPU_ROUTER_PORT"] = str(port)
            env["MXTPU_ROUTER_REPLICAS"] = addrs
            # per-replica file sinks: rank i+1 suffixes telemetry/
            # profiler outputs .r<i+1> (telemetry.rank_suffixed) so N
            # replicas on one host never write over one file, and the
            # ROUTER side stays the unsuffixed rank-0 base that
            # tools/obs_stitch.py aligns replica traces onto
            # (docs/observability.md "Request tracing & SLOs")
            env["MXTPU_PROCESS_ID"] = str(i + 1)
            env["PYTHONPATH"] = (repo_root + os.pathsep
                                 + os.environ.get("PYTHONPATH", ""))
            env.update(chip_envs[i])
            procs.append(subprocess.Popen(args.command, env=env))
        rc = 0
        try:
            for p in procs:
                rc |= p.wait()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
        sys.exit(rc)

    if args.num_workers is None:
        parser.error("-n/--num-workers is required (except with "
                     "--serve-replicas)")
    if args.elastic:
        # elastic supervision is pure-SPMD: the PS control plane has no
        # membership-change story (server state would be lost with the
        # generation), so servers are refused rather than half-working
        if not args.local_spmd:
            parser.error("--elastic requires --local-spmd")
        if args.num_servers:
            parser.error("--elastic requires -s 0 (no parameter servers)")
        args.num_servers = 0
        sys.exit(_run_elastic(args, repo_root,
                              _local_spmd_env(args, parser)))
    if args.num_servers is None:
        args.num_servers = args.num_workers
    if args.local_spmd and args.launcher != "local":
        parser.error("--local-spmd implies the local launcher")
    base_env = {
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(_free_port()),
        # make the framework importable in spawned roles regardless of cwd
        # (parity: reference tools/launch.py inserting curr_path)
        "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }

    if args.local_spmd:
        # one jax.distributed coordinator port for the SPMD mesh, one
        # DMLC port for the (optional) parameter-server control plane —
        # both on this host; each worker is one mesh process
        base_env["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % _free_port()
        base_env.update(_local_spmd_env(args, parser))
        if args.obs and not os.environ.get("MXTPU_OBS_PORT"):
            # a third port for the rank-0 observability aggregator
            # (obs/aggregate.py); an operator-exported port passes
            # through the environment untouched
            base_env["MXTPU_OBS_PORT"] = str(_free_port())
    elif args.obs:
        parser.error("--obs requires --local-spmd")

    if args.launcher == "local":
        procs = []
        # several parameter-server workers on one chip host: one chip
        # each (a lone worker, or SPMD ranks, keep what _local_spmd_env
        # decided)
        chip_envs = None
        if args.num_workers > 1 and not args.local_spmd:
            chip_envs = _one_chip_envs(args.num_workers, parser,
                                       "-n %d" % args.num_workers)

        def spawn(role, rank=None, index=0):
            env = dict(os.environ)
            env.update(base_env)
            _role_env(role, env)
            if role == "worker" and chip_envs:
                env.update(chip_envs[index])
            if rank is not None:
                env["MXTPU_PROCESS_ID"] = str(rank)
                env["DMLC_WORKER_ID"] = str(rank)
            if role != "worker":
                cmd = [sys.executable, "-c", _SERVER_BOOTSTRAP]
            else:
                cmd = args.command
            return subprocess.Popen(cmd, env=env)

        if args.num_servers > 0:
            procs.append(spawn("scheduler"))
            for _ in range(args.num_servers):
                procs.append(spawn("server"))
        workers = [spawn("worker", rank=i if args.local_spmd else None,
                         index=i)
                   for i in range(args.num_workers)]
        rc = 0
        for p in workers:
            rc |= p.wait()
        for p in procs:
            p.terminate()
        sys.exit(rc)

    if args.launcher == "mpi":
        # scheduler local; one mpirun per role set (reference
        # dmlc_tracker/mpi.py submit(): separate worker/server launches,
        # env forwarded per MPI flavor).  MXTPU_MPIRUN overrides the
        # binary so tests can shim it without an MPI install.
        mpirun = os.environ.get("MXTPU_MPIRUN", "mpirun")
        sched = _spawn_local_scheduler(base_env)

        def mpi_cmd(role, n, cmd):
            argv = [mpirun, "-n", str(n)]
            if args.hostfile:
                # OpenMPI's mpirun takes --hostfile; MPICH's Hydra takes -f
                flag = "--hostfile" if args.mpi_flavor == "openmpi" else "-f"
                argv += [flag, args.hostfile]
            env = _role_env(role, dict(base_env))
            if args.mpi_flavor == "openmpi":
                for k, v in env.items():
                    argv += ["-x", "%s=%s" % (k, v)]
            else:
                for k, v in env.items():
                    argv += ["-genv", k, v]
            return argv + cmd

        server_cmd = [sys.executable, "-c", _SERVER_BOOTSTRAP]
        servers = subprocess.Popen(
            mpi_cmd("server", args.num_servers, server_cmd))
        workers = subprocess.Popen(
            mpi_cmd("worker", args.num_workers, args.command))
        rc = workers.wait()
        for p in (servers, sched):
            p.terminate()
        sys.exit(rc)

    if args.launcher == "yarn":
        parser.error(
            "yarn launching is not supported: this framework's DCN "
            "scale-out paths are the TCP parameter server (local/ssh/mpi/"
            "sge launchers) and jax.distributed multi-host SPMD "
            "(parallel/multihost.py); submit those through your cluster's "
            "own job wrapper")

    if args.launcher == "sge":
        # scheduler local; one qsub ARRAY JOB per role set (reference
        # dmlc_tracker/sge.py).  MXTPU_QSUB overrides the binary so tests
        # can shim it without a grid engine install.
        import shlex
        import tempfile

        qsub = os.environ.get("MXTPU_QSUB", "qsub")
        sched = _spawn_local_scheduler(base_env)
        scripts = []

        def submit(role, count, cmd):
            script = tempfile.NamedTemporaryFile(
                "w", suffix=".sh", prefix="mxtpu_%s_" % role, delete=False)
            scripts.append(script.name)
            lines = ["#!/bin/sh"]
            lines += ["export %s=%s" % (k, shlex.quote(v))
                      for k, v in _role_env(role, dict(base_env)).items()]
            lines.append("exec %s" % " ".join(shlex.quote(c) for c in cmd))
            script.write("\n".join(lines) + "\n")
            script.close()
            os.chmod(script.name, 0o755)
            argv = [qsub, "-t", "1-%d" % count, "-cwd", "-V", "-b", "n"]
            if args.sge_queue:
                argv += ["-q", args.sge_queue]
            # qsub output goes to a FILE, not a pipe: grid jobs (or shim
            # children) inheriting a pipe would block this read past
            # qsub's own exit
            with tempfile.TemporaryFile("w+") as qout:
                subprocess.run(argv + [script.name], check=True,
                               stdout=qout, stderr=subprocess.STDOUT)
                qout.seek(0)
                out = qout.read()
            # "Your job-array <id>.…" — remember ids so failures qdel
            for tok in out.split():
                if tok.split(".")[0].isdigit():
                    job_ids.append(tok.split(".")[0])
                    break

        job_ids = []
        rc = 1  # submit/wait failures surface as nonzero
        try:
            submit("server", args.num_servers,
                   [sys.executable, "-c", _SERVER_BOOTSTRAP])
            submit("worker", args.num_workers, args.command)
            # qsub is asynchronous: completion is observed through the
            # scheduler, which exits 0 only when every worker FINALIZEd
            # cleanly (dist.run_scheduler)
            rc = sched.wait()
        finally:
            if sched.poll() is None:
                sched.terminate()
            if rc != 0 and job_ids:
                # cancel still-queued/running array jobs (best effort)
                qdel = os.environ.get("MXTPU_QDEL", "qdel")
                subprocess.run([qdel] + job_ids, capture_output=True)
            for sc in scripts:
                try:
                    os.unlink(sc)
                except OSError:
                    pass
        sys.exit(rc)

    # ssh launcher
    hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
    base_env["DMLC_PS_ROOT_URI"] = hosts[0]
    procs = []

    def ssh_spawn(host, role):
        env_str = " ".join("%s=%s" % (k, v) for k, v in
                           _role_env(role, dict(base_env)).items())
        if role != "worker":
            remote = "python -c %r" % _SERVER_BOOTSTRAP
        else:
            remote = " ".join(args.command)
        cwd = args.sync_dst_dir or os.getcwd()
        return subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             "cd %s && env %s %s" % (cwd, env_str, remote)]
        )

    procs.append(ssh_spawn(hosts[0], "scheduler"))
    for i in range(args.num_servers):
        procs.append(ssh_spawn(hosts[i % len(hosts)], "server"))
    workers = [ssh_spawn(hosts[i % len(hosts)], "worker") for i in range(args.num_workers)]
    rc = 0
    for p in workers:
        rc |= p.wait()
    for p in procs:
        p.terminate()
    sys.exit(rc)


if __name__ == "__main__":
    main()
