"""Multi-host launcher helper.

Counterpart of ``apex/parallel/multiproc.py:1-36`` (trivial one-node
launcher: one process per GPU with ``--rank`` args). TPU pods invert the
model — one process per *host*, all chips of that host in-process, and
``jax.distributed`` stitches hosts into one global device set — so the
launcher's job collapses to environment-driven initialization:

    python -m apex_tpu.parallel.multiproc train.py ...

initializes ``jax.distributed`` from the standard env vars
(``COORDINATOR_ADDRESS``, ``NUM_PROCESSES``, ``PROCESS_ID`` — or the TPU
metadata auto-detection when unset) and ``exec``s the script, which then
sees the full multi-host ``jax.devices()``.
"""

from __future__ import annotations

import os
import runpy
import sys

__all__ = ["init_distributed", "main"]


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None) -> int:
    """Initialize jax.distributed (idempotent); returns process count.

    On TPU pods with no explicit args, ``jax.distributed.initialize()``
    auto-detects topology from the metadata server.
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    except RuntimeError as e:
        # a second call in one process is a no-op here, anything else is
        # an error (jax: "distributed.initialize should only be called
        # once.")
        if "only be called once" not in str(e):
            raise
    return jax.process_count()


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(__doc__)
        raise SystemExit(
            "usage: python -m apex_tpu.parallel.multiproc SCRIPT [args...]")
    n = init_distributed()
    print(f"apex_tpu.multiproc: {n} process(es) joined", flush=True)
    script, sys.argv = argv[0], argv
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
