#!/usr/bin/env python
"""Distributed launcher (reference: ``tools/launch.py`` over dmlc-core
trackers).

TPU-native redesign: there are no parameter-server/scheduler roles --
every worker is a ``jax.distributed`` process and gradient reduction is
an XLA collective over ICI/DCN (see ``mxnet_tpu/kvstore.py``).  The
launcher therefore only has to start N identical processes with the
coordinator's address and each process's index:

  local mode:   ``launch.py -n 4 python train.py``      (one host, CPU)
  ssh mode:     ``launch.py -n 8 -H hostfile python train.py``
  supervised:   ``launch.py -n 4 --supervise python train.py``

Local mode is the CPU/gloo test path.  A TPU chip belongs to one
process, and one process drives every chip of its host (``python
train.py`` over ``parallel.make_mesh``); N local workers with one
environment would each open every chip.  On a host with TPU device
nodes local mode therefore refuses to start unless the workers are
pinned to the CPU (``JAX_PLATFORMS=cpu``).  This launcher imports no
JAX and holds no chip itself.

Each worker gets MXNET_TPU_COORDINATOR / MXNET_TPU_NUM_PROCS /
MXNET_TPU_PROC_ID; ``mxnet_tpu.distributed_init()`` (or user code) maps
them onto ``jax.distributed.initialize``.

``--supervise`` (local mode) routes through the elastic restart
supervisor (``mxnet_tpu.supervisor``): a rank death tears the world
down (survivors get their typed BarrierTimeout within ``--grace``),
the generation id is bumped (MXNET_TPU_GENERATION -- workers resume
via ``ContinuousTrainer.resume()``), and the world relaunches under a
bounded ``--max-restarts`` budget.
"""
from __future__ import annotations

import argparse
import glob
import os
import shlex
import socket
import subprocess
import sys
import threading

_print_lock = threading.Lock()


def _relay(pipe, prefix):
    """Line-buffered prefixed relay (the dmlc tracker behavior): each
    worker line becomes ONE atomic write under a lock, so two workers'
    output can never interleave mid-line."""
    out = sys.stdout.buffer
    with pipe:
        for line in iter(pipe.readline, b""):
            if not line.endswith(b"\n"):
                line += b"\n"
            with _print_lock:
                out.write(prefix + line)
                out.flush()


def _spawn_relayed(cmd, env, rank):
    p = subprocess.Popen(cmd, env=env, start_new_session=True,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT)
    t = threading.Thread(target=_relay,
                         args=(p.stdout, b"[%d] " % rank), daemon=True)
    t.start()
    p._relay_thread = t
    return p


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _kill_tree(procs):
    """SIGTERM each worker's whole process group (workers start in
    their own session, so wrapper scripts' grandchildren die too),
    escalating to SIGKILL after a grace period."""
    import signal
    import time
    for q in procs:
        try:
            os.killpg(q.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            q.terminate()
    deadline = time.time() + 10
    for q in procs:
        try:
            q.wait(timeout=max(0.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            pass
        if q.poll() is None:
            try:
                os.killpg(q.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                q.kill()
            q.wait()


def _wait_all(procs):
    """Wait for every worker, failing FAST: the first nonzero exit
    tears down the survivors (a dead peer would otherwise wedge the
    rest inside jax.distributed collectives); Ctrl-C tears all down."""
    import time
    try:
        while procs:
            for p in list(procs):
                rc = p.poll()
                if rc is None:
                    continue
                procs.remove(p)
                t = getattr(p, "_relay_thread", None)
                if t is not None:
                    t.join(timeout=10)
                if rc != 0:
                    _kill_tree(procs)
                    return rc
            # fail-FAST over N children needs a poll round-robin: a
            # blocking wait on any single child would hide a sibling's
            # death behind it (os.wait reaps relay threads' pipes too)
            time.sleep(0.1)  # mxlint: disable=sleep-poll
        return 0
    except KeyboardInterrupt:
        _kill_tree(procs)
        raise


def _refuse_local_on_tpu_host():
    """Why local mode must not start here, or None.  Observed without
    JAX: the device nodes libtpu opens (``/dev/accel*`` up to v4,
    ``/dev/vfio/<n>`` from v5e on)."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    nodes = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if not nodes:
        return None
    return ("local mode would start N workers that each open every TPU "
            "chip of this host (%s): a chip belongs to one process.  Run "
            "ONE process per host -- it drives all local chips through "
            "parallel.make_mesh -- or export JAX_PLATFORMS=cpu for the "
            "CPU/gloo test path (docs/distributed.md)."
            % ", ".join(sorted(nodes)))


def launch_local(args, command):
    coord = "127.0.0.1:%d" % _free_port()
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "MXNET_TPU_COORDINATOR": coord,
            "MXNET_TPU_NUM_PROCS": str(args.num_workers),
            "MXNET_TPU_PROC_ID": str(rank),
            # legacy names some scripts read
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(args.num_workers),
        })
        procs.append(_spawn_relayed(command, env, rank))
    return _wait_all(procs)


def launch_ssh(args, command):
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()
                 and not h.startswith("#")]
    if len(hosts) < args.num_workers:
        # round-robin workers over hosts
        hosts = [hosts[i % len(hosts)] for i in range(args.num_workers)]
    # per-job coordinator port: a fixed port would collide across jobs
    # (or a restart racing its predecessor's TIME_WAIT socket)
    port = args.port or (40000 + os.getpid() % 20000)
    coord = "%s:%d" % (hosts[0].split(":")[0], port)
    procs = []
    cwd = os.getcwd()
    for rank in range(args.num_workers):
        host = hosts[rank].split(":")[0]
        envs = " ".join("%s=%s" % kv for kv in [
            ("MXNET_TPU_COORDINATOR", coord),
            ("MXNET_TPU_NUM_PROCS", str(args.num_workers)),
            ("MXNET_TPU_PROC_ID", str(rank)),
        ])
        remote = "cd %s && env %s %s" % (
            shlex.quote(cwd), envs, " ".join(map(shlex.quote, command)))
        procs.append(_spawn_relayed(
            ["ssh", "-o", "StrictHostKeyChecking=no", "-tt", host,
             remote], None, rank))
    # -tt allocates a tty so terminating the ssh client also kills the
    # remote command instead of orphaning it
    return _wait_all(procs)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-H", "--hostfile", default=None,
                   help="one host per line; omit for single-host local")
    p.add_argument("--port", type=int, default=0,
                   help="coordinator port for ssh mode (default: derived "
                        "per job)")
    p.add_argument("--supervise", action="store_true",
                   help="elastic restart supervision (local mode): on "
                        "any rank exit, tear down, bump the generation "
                        "id, and relaunch under --max-restarts")
    p.add_argument("--max-restarts", type=int, default=None,
                   help="restart budget for --supervise (default: "
                        "MXNET_TPU_SUPERVISOR_RESTARTS)")
    p.add_argument("--grace", type=float, default=None,
                   help="seconds survivors get to exit on their own "
                        "typed error before the tree is killed "
                        "(default: MXNET_TPU_SUPERVISOR_GRACE_S)")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if not args.command:
        p.error("no command given")
    if not args.hostfile:
        why = _refuse_local_on_tpu_host()
        if why:
            p.error(why)
    if args.supervise:
        if args.hostfile:
            p.error("--supervise is local-mode only (ssh worlds need "
                    "an external supervisor per host)")
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from mxnet_tpu.supervisor import Supervisor
        return Supervisor(args.command, args.num_workers,
                          max_restarts=args.max_restarts,
                          grace_s=args.grace).run()
    if args.hostfile:
        return launch_ssh(args, args.command)
    return launch_local(args, args.command)


if __name__ == "__main__":
    sys.exit(main())
