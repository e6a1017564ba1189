"""Chain-server entrypoint: ``python -m generativeaiexamples_tpu.server``.

Replaces the reference's ``uvicorn RetrievalAugmentedGeneration.common.
server:app`` entrypoint (reference: RetrievalAugmentedGeneration/
Dockerfile:57).
"""
import argparse
import os

from aiohttp import web

from generativeaiexamples_tpu.server.api import create_app
from generativeaiexamples_tpu.utils import jax_env


def main() -> None:
    parser = argparse.ArgumentParser(description="TPU RAG chain-server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=int(os.environ.get("APP_SERVERPORT", 8081)))
    parser.add_argument(
        "--help-config",
        action="store_true",
        help="print the config schema with APP_* env names and exit "
        "(reference: frontend/__main__.py:36-41)",
    )
    args = parser.parse_args()
    if args.help_config:
        from generativeaiexamples_tpu.config.schema import AppConfig

        import sys

        AppConfig.print_help(sys.stdout.write)
        return
    jax_env.bootstrap()
    # On SIGTERM, streams still open get 15 s (aiohttp's default is 60) and
    # are then cancelled: an answer of thousands of tokens does not finish
    # inside any grace period (planned handover is POST /internal/drain),
    # and a handler still waiting for its first token would hold the
    # process, and whoever waits for its exit, for the whole of it.
    web.run_app(create_app(), host=args.host, port=args.port, shutdown_timeout=15.0)


if __name__ == "__main__":
    main()
