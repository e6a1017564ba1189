"""Router entry point.

    python -m generativeaiexamples_tpu.router --port 9000 \
        --replica http://127.0.0.1:8081 --replica http://127.0.0.1:8082

``--replica`` flags override the ``router.replicas`` config list
(``APP_ROUTER_REPLICAS``); ``--policy`` overrides ``router.policy``.
"""
from __future__ import annotations

import argparse

from aiohttp import web


def main() -> None:
    parser = argparse.ArgumentParser(
        description="Cache-aware multi-replica routing tier"
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=9000)
    parser.add_argument(
        "--replica", action="append", default=[],
        help="replica base URL (repeatable; overrides router.replicas)",
    )
    parser.add_argument(
        "--policy", default="", choices=("", "affinity", "round_robin"),
        help="placement policy override",
    )
    args = parser.parse_args()

    from generativeaiexamples_tpu.config import get_config
    from generativeaiexamples_tpu.router.app import create_router_app
    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()

    config = get_config()
    if args.policy:
        object.__setattr__(config.router, "policy", args.policy)
    app = create_router_app(config, replica_urls=args.replica or None)
    web.run_app(app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
