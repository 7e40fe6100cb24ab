#!/usr/bin/env python
"""Multi-host job launcher (reference: tools/launch.py:71-73 — dmlc-tracker
local/ssh/mpi/sge/yarn launchers spawning scheduler + servers + workers).

TPU-native: there is no parameter-server topology — every host runs the SAME
SPMD program and JAX's coordination service replaces the dmlc scheduler.
Supported launchers:
- `local`: spawn N worker processes on this machine wired together via
  `jax.distributed` env (JAX_COORDINATOR_ADDRESS/PROCESS_ID/NUM_PROCESSES).
  CPU-only multi-process on one host is for testing the multi-host code path.
- `ssh`: print (or run) the per-host command list for a host file; on real
  TPU pods the platform runtime (e.g. GKE/QR) usually injects these envs.
"""
import argparse
import os
import subprocess
import sys


def launch_local(n, command, coordinator="127.0.0.1:12345", num_servers=0,
                 server_port=9091):
    server_procs = []
    ps_env = {}
    if num_servers:
        # dist_async topology: N parameter-server processes on
        # consecutive ports (server i at server_port + i); workers learn
        # the topology through the reference DMLC env protocol and shard
        # big arrays across all of them (kvstore_async.py PSKV placement)
        ps_env = {"DMLC_PS_ROOT_URI": "127.0.0.1",
                  "DMLC_PS_ROOT_PORT": str(server_port),
                  "DMLC_NUM_SERVER": str(num_servers)}
        # the server module must import regardless of the caller's cwd
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for sid in range(num_servers):
            env = dict(os.environ)
            env.update(ps_env)
            env.update({"DMLC_ROLE": "server", "DMLC_SERVER_ID": str(sid),
                        "DMLC_NUM_WORKER": str(n),
                        "MXNET_KVSTORE_TYPE": "dist_async"})
            # the parameter server is a HOST-side component: pin it to the
            # CPU backend so it never asks for a chip a worker holds
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            server_procs.append(subprocess.Popen(
                [sys.executable, "-m", "mxnet_tpu.kvstore_server"],
                env=env, shell=False))
        # gate on server health BEFORE spawning workers: a dead server
        # (EADDRINUSE from a stale run is the classic) must abort the
        # launch loudly, not leave workers dialing a wrong/stale server
        import socket as _socket
        import time as _time
        deadline = _time.time() + 30.0
        for sid, server in enumerate(server_procs):
            port = server_port + sid
            while True:
                if server.poll() is not None:
                    raise SystemExit(
                        "dist_async parameter server %d exited rc=%d before "
                        "accepting (stale server still on port %d?)"
                        % (sid, server.returncode, port))
                try:
                    _socket.create_connection(("127.0.0.1", port),
                                              timeout=1.0).close()
                    break
                except OSError:
                    if _time.time() > deadline:
                        for p in server_procs:
                            p.terminate()
                        raise SystemExit(
                            "dist_async parameter server %d did not "
                            "accept within 30s" % sid)
                    _time.sleep(0.2)
        # the accepting socket could be a STALE server from a previous
        # run while ours is still dying of EADDRINUSE — let the bind
        # settle and re-check our processes actually own the ports
        _time.sleep(1.0)
        for sid, server in enumerate(server_procs):
            if server.poll() is not None:
                raise SystemExit(
                    "dist_async parameter server %d exited rc=%d right "
                    "after startup — another server is likely holding "
                    "port %d" % (sid, server.returncode, server_port + sid))
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(ps_env)
        env.update({
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(n),
            "JAX_PROCESS_ID": str(rank),
            # DMLC-compat aliases (reference env protocol, kvstore.h:254)
            "DMLC_NUM_WORKER": str(n),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_ROLE": "worker",
        })
        procs.append(subprocess.Popen(command, env=env, shell=False))
    rc = 0
    for p in procs:
        rc |= p.wait()
    for p in server_procs:  # workers done: the server has nothing to serve
        p.terminate()
        p.wait()
    return rc


def launch_ssh(hostfile, command, coordinator_port=12345, dry_run=True):
    with open(hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    n = len(hosts)
    coordinator = "%s:%d" % (hosts[0], coordinator_port)
    cmds = []
    for rank, host in enumerate(hosts):
        envs = ("JAX_COORDINATOR_ADDRESS=%s JAX_NUM_PROCESSES=%d "
                "JAX_PROCESS_ID=%d" % (coordinator, n, rank))
        cmds.append(["ssh", host, "%s %s" % (envs, " ".join(command))])
    if dry_run:
        for c in cmds:
            print(" ".join(c))
        return 0
    procs = [subprocess.Popen(c) for c in cmds]
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--coordinator-port", type=int, default=12345)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="parameter-server processes for dist_async "
                             "(keys shard across all of them; sync "
                             "kvstores need none)")
    parser.add_argument("--server-port", type=int, default=9091)
    parser.add_argument("--run-ssh", action="store_true",
                        help="actually exec over ssh instead of printing")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    command = [c for c in args.command if c != "--"]
    if not command:
        parser.error("no command given")
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, command,
                              "127.0.0.1:%d" % args.coordinator_port,
                              num_servers=args.num_servers,
                              server_port=args.server_port))
    if not args.hostfile:
        parser.error("ssh launcher needs --hostfile")
    sys.exit(launch_ssh(args.hostfile, command, args.coordinator_port,
                        dry_run=not args.run_ssh))


if __name__ == "__main__":
    main()
