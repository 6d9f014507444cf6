#!/usr/bin/env python
"""Cluster launcher (ref: tools/launch.py + 3rdparty/dmlc-core/tracker/
dmlc_tracker — local/ssh launch of scheduler+servers+workers with
DMLC_* env rendezvous).

TPU-native redesign: there are no scheduler or server roles — every
process is an SPMD worker and process 0 doubles as the jax.distributed
coordinator. This launcher assigns the same DMLC_* env contract the
reference's tracker used, so `launch.py -n 4 python train.py` ports
unchanged:

    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT  coordinator address
    DMLC_NUM_WORKER                       number of worker processes
    DMLC_WORKER_ID                        this process's id
    DMLC_ROLE=worker

Launchers:
  local  fork N workers on this host (the dmlc_tracker/local.py
         analogue; also how the multi-process tests simulate
         multi-host, SURVEY.md §4 pattern 4)
  ssh    one worker per host from --host-file via ssh (the
         dmlc_tracker/ssh.py analogue)

`-s/--num-servers` is accepted for command-line parity and must be 0:
parameter servers do not exist in the SPMD design.

One process per chip: this launcher must never import JAX (or
mxnet_tpu, which does). A parent that has touched JAX holds the chip,
and the worker that needs it then fails or hangs. It imports only the
standard library — keep it so.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(args, worker_id: int, uri: str, port: int):
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": uri,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": "0",
        "DMLC_WORKER_ID": str(worker_id),
    })
    if args.cpu_devices:
        env["MXNET_DIST_CPU_DEVICES"] = str(args.cpu_devices)
    return env


def _wait_all(procs) -> int:
    """Wait for every worker; if one fails, terminate the rest (they
    would otherwise block forever in the next collective)."""
    import time
    try:
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                return max(abs(c) for c in codes) if any(codes) else 0
            if any(c not in (None, 0) for c in codes):
                time.sleep(1.0)  # grace for siblings to exit on their own
                for p in procs:
                    if p.poll() is None:
                        p.send_signal(signal.SIGTERM)
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                return max(abs(c) for c in (p.poll() or 0 for p in procs)) or 1
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)


def launch_local(args, command) -> int:
    uri, port = "127.0.0.1", _free_port()
    procs = []
    try:
        for wid in range(args.num_workers):
            procs.append(subprocess.Popen(
                command, env=_worker_env(args, wid, uri, port)))
    except Exception:
        for p in procs:  # don't leak half a rendezvous
            if p.poll() is None:
                p.kill()
        raise
    return _wait_all(procs)


def launch_ssh(args, command) -> int:
    with open(args.host_file) as f:
        hosts = [h.strip() for h in f if h.strip() and not h.startswith("#")]
    if len(hosts) < args.num_workers:
        raise SystemExit("host file has %d hosts < -n %d"
                         % (len(hosts), args.num_workers))
    uri = hosts[0]
    port = args.port or 9091
    procs = []
    cwd = os.getcwd()
    for wid in range(args.num_workers):
        env = _worker_env(args, wid, uri, port)
        exports = " ".join("%s=%s" % (k, v) for k, v in env.items()
                           if k.startswith(("DMLC_", "MXNET_")))
        remote = "cd %s && env %s %s" % (cwd, exports,
                                         " ".join(command))
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no",
                                       hosts[wid], remote]))
    return _wait_all(procs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="launch a multi-process mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI parity; must be 0")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--host-file", help="one host per line (ssh)")
    ap.add_argument("--port", type=int, help="coordinator port (ssh)")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU devices per worker (testing)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.num_servers:
        raise SystemExit(
            "-s/--num-servers must be 0: the SPMD design has no "
            "parameter-server processes (see mxnet_tpu.dist)")
    if not args.command:
        raise SystemExit("no command given")
    if args.launcher == "local":
        return launch_local(args, args.command)
    if not args.host_file:
        raise SystemExit("ssh launcher needs --host-file")
    return launch_ssh(args, args.command)


if __name__ == "__main__":
    sys.exit(main())
