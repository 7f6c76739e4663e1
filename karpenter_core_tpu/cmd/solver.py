"""TPU solver sidecar entrypoint: ``python -m karpenter_core_tpu.cmd.solver``.

Runs the gRPC snapshot channel (service.snapshot_channel) on the TPU host —
the second container of the deployed pair (BASELINE.json north-star split:
controller plane where it is, solves on the accelerator).  Persistent compile
caches make sidecar restarts cheap; the first request on a fresh machine pays
the one-time compile.

Env:
  KC_SOLVER_LISTEN    bind address (default 0.0.0.0:8980)
  CLOUD_PROVIDER      module:attr of the CloudProvider (default: fake)
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import threading

from karpenter_core_tpu.cmd.operator import load_cloud_provider


def compose(provider=None, address=None):
    """Start the sidecar the way the deployed binary does; returns
    ``(server, bound_port)``.  ``main()`` calls it with the env-configured
    provider and address; chip_smoke.py calls it with its own catalog and a
    loopback port, so the smoke drives the same composition users run."""
    from karpenter_core_tpu.service.snapshot_channel import serve

    if provider is None:
        provider = load_cloud_provider(
            os.environ.get(
                "CLOUD_PROVIDER",
                "karpenter_core_tpu.cloudprovider.fake:FakeCloudProvider",
            )
        )
    if address is None:
        address = os.environ.get("KC_SOLVER_LISTEN", "0.0.0.0:8980")
    return serve(provider, address=address)


def main() -> int:
    logging.basicConfig(
        level=os.environ.get("LOG_LEVEL", "INFO").upper(),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    server, port = compose()
    logging.getLogger(__name__).info("tpu solver sidecar listening on :%d", port)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    server.stop(grace=5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
