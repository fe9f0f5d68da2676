#!/usr/bin/env python
"""Distributed job launcher — the ``tools/launch.py`` analog.

Capability parity with reference ``tools/launch.py`` + the dmlc-core local
tracker: spawn N worker processes for a distributed training command, wiring
the rendezvous environment each worker's ``kvstore.create('dist_*')`` /
``parallel.init_distributed()`` reads.

TPU-native redesign: the reference tracker starts a scheduler plus servers
and workers and coordinates them over ZMQ (``DMLC_PS_ROOT_URI`` et al.).
XLA collectives are SPMD — there is no parameter server — so the launcher
spawns WORKERS ONLY and the "scheduler" is jax.distributed's coordination
service bound by worker 0. The reference's DMLC_* names are still exported
(mapped onto the jax settings) so reference-style launch scripts keep
working; ``-s/--num-servers`` is accepted and ignored with a note.

One process per chip: a chip belongs to one process at a time, and N
local workers that each asked for "the TPU" would fight over it. This
launcher never imports jax (it holds no chip itself), and its local
workers are CPU-only by explicit environment (``JAX_PLATFORMS=cpu``)
unless the caller has named a platform. All the chips of one host are
driven by ONE process (``parallel.SPMDTrainer`` over ``jax.devices()``),
not by one launcher worker each.

Usage (matches the reference's local launcher):
    python tools/launch.py -n 4 [--launcher local] python train.py ...
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(num_workers: int, command, extra_env=None,
                 host: str = "127.0.0.1", port: int = 0) -> int:
    """Spawn ``num_workers`` local processes running ``command``; returns the
    first nonzero exit code (0 if all succeed). The multi-process-on-one-box
    pattern is the reference's own CI strategy for distributed tests
    (tests/nightly/dist_sync_kvstore.py)."""
    port = port or _free_port()
    procs = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update(extra_env or {})
        # one process per chip: local workers share this host, so they
        # stay off the chip unless the caller says otherwise
        env.setdefault("JAX_PLATFORMS", "cpu")
        # reference DMLC tracker names, mapped onto jax.distributed
        env["DMLC_ROLE"] = "worker"
        env["DMLC_PS_ROOT_URI"] = host
        env["DMLC_PS_ROOT_PORT"] = str(port)
        env["DMLC_NUM_WORKER"] = str(num_workers)
        env["DMLC_WORKER_ID"] = str(rank)
        # native names (read by parallel.init_distributed)
        env["MXTPU_COORDINATOR"] = f"{host}:{port}"
        env["MXTPU_NUM_WORKERS"] = str(num_workers)
        env["MXTPU_WORKER_RANK"] = str(rank)
        procs.append(subprocess.Popen(list(command), env=env))
    rc = 0
    for p in procs:
        p.wait()
        if p.returncode != 0 and rc == 0:
            rc = p.returncode
    if rc != 0:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI parity; XLA SPMD has "
                         "no parameter servers, so this is ignored")
    ap.add_argument("--launcher", default="local", choices=["local"],
                    help="only the local (multi-process one box) tracker "
                         "is built in; ssh/mpi/yarn would wrap this same "
                         "environment protocol")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.num_servers:
        print("note: -s/--num-servers ignored (SPMD collectives replace "
              "the parameter server)", file=sys.stderr)
    if not args.command:
        ap.error("no command given")
    return launch_local(args.num_workers, args.command,
                        host=args.host, port=args.port)


if __name__ == "__main__":
    sys.exit(main())
