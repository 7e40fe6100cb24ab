#!/usr/bin/env python
"""Diagnose the current system for issue reports.

Reference: tools/diagnose.py (OS / hardware / python / pip / mxnet /
network sections). TPU-native differences: the framework section reports
the JAX backend and device inventory instead of a libmxnet build, the
accelerator probe runs in a TIMEOUT-GUARDED child (a chip another process
holds makes the probe fail or hang — a diagnosis tool must report that,
not hang on it; this parent never imports jax, so it never holds the chip
itself), and network checks are opt-in (zero-egress environments are the
norm here).

Usage: python tools/diagnose.py [--network 1] [--timeout 15]
"""
from __future__ import annotations

import argparse
import os
import platform
import subprocess
import sys
import time


def section(title):
    print("----------%s Info----------" % title)


def check_python():
    section("Python")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    section("Pip")
    try:
        import pip
        print("Version      :", pip.__version__)
        print("Directory    :", os.path.dirname(pip.__file__))
    except ImportError:
        print("No corresponding pip install for current python.")


def check_os():
    section("Platform")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_hardware():
    section("Hardware")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor())
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.run(["lscpu"], capture_output=True, text=True,
                                 timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Architecture", "CPU(s)",
                                           "Model name", "Thread",
                                           "MHz")):
                    print(line.strip())
        except (OSError, subprocess.TimeoutExpired):
            pass


def check_framework(timeout):
    """Import + device probe in a BUDGETED subprocess: a chip held by
    another process can hang jax.devices(), and that hang is itself the
    diagnosis worth reporting."""
    section("MXNet-TPU")
    code = (
        "import time, json\n"
        "t0 = time.time()\n"
        "import mxnet_tpu as mx\n"
        "import jax\n"
        "devs = [(d.platform, getattr(d, 'device_kind', '')) "
        "for d in jax.devices()]\n"
        "x = (jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))\n"
        "jax.block_until_ready(x)\n"
        "print(json.dumps({'version': mx.__version__, 'jax': jax.__version__,"
        " 'devices': devs, 'probe_s': round(time.time() - t0, 2)}))\n")
    t0 = time.time()
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode == 0:
            print("Probe        :", proc.stdout.strip().splitlines()[-1])
        else:
            print("Import/probe FAILED:")
            print(proc.stderr.strip()[-1000:])
    except subprocess.TimeoutExpired:
        print("Probe HUNG past %.0fs — accelerator backend unreachable or "
              "held by another process (run with JAX_PLATFORMS=cpu to "
              "bypass)" % (time.time() - t0))
    from importlib.util import find_spec
    print("Directory    :", os.path.dirname(
        find_spec("mxnet_tpu").origin) if find_spec("mxnet_tpu") else "?")
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        commit = subprocess.run(["git", "rev-parse", "HEAD"], cwd=repo,
                                capture_output=True, text=True,
                                timeout=10).stdout.strip()
        if commit:
            print("Commit Hash  :", commit)
    except (OSError, subprocess.SubprocessError):
        pass  # a hung git must not kill the diagnostic report


def check_network(timeout):
    section("Network")
    import socket
    hosts = {"PYPI": "pypi.python.org", "Github": "github.com",
             "S3": "s3.amazonaws.com"}
    for name, host in hosts.items():
        t0 = time.time()
        try:
            socket.create_connection((host, 443), timeout=timeout).close()
            print("Timing the connection to %s: %.4f sec"
                  % (name, time.time() - t0))
        except OSError as e:
            print("Error connecting to %s (%s): %s" % (name, host, e))


def check_environment():
    section("Environment")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "DMLC_", "OMP_")):
            print("%-28s %s" % (k, v))


def main():
    ap = argparse.ArgumentParser(
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
        description="Diagnose the current system.")
    for choice in ("python", "pip", "mxnet", "os", "hardware",
                   "environment"):
        ap.add_argument("--" + choice, default=1, type=int,
                        help="Diagnose %s" % choice)
    ap.add_argument("--network", default=0, type=int,
                    help="Diagnose network (off by default: zero-egress "
                         "environments)")
    ap.add_argument("--timeout", default=15, type=float,
                    help="Budget for the accelerator/network probes")
    args = ap.parse_args()
    if args.python:
        check_python()
    if args.pip:
        check_pip()
    if args.mxnet:
        check_framework(args.timeout)
    if args.os:
        check_os()
    if args.hardware:
        check_hardware()
    if args.environment:
        check_environment()
    if args.network:
        check_network(args.timeout)


if __name__ == "__main__":
    main()
