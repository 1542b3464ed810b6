"""`accelerate-tpu` / `atx` CLI entry point.

Analog of the reference `commands/accelerate_cli.py:27-48` subcommand
registry. Subcommands are registered lazily so importing the CLI stays cheap;
full implementations arrive with the launcher milestone (`commands/launch.py`,
`commands/config.py`, ...).
"""

from __future__ import annotations

import argparse
import sys


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="accelerate-tpu",
        description="TPU-native training & inference framework CLI",
    )
    subparsers = parser.add_subparsers(dest="command")

    import importlib

    for name in (
        "env", "config", "launch", "estimate", "lint", "serve", "test",
        "merge", "tpu", "chaos", "trace",
    ):
        try:
            module = importlib.import_module(f".{name}", package=__package__)
        except ImportError as e:
            # Only tolerate the subcommand module itself being absent; a
            # broken import *inside* an existing module must surface.
            if e.name == f"{__package__}.{name}":
                continue
            raise
        module.register(subparsers)

    args = parser.parse_args(argv)
    if args.command is None:
        parser.print_help()
        return 1
    from ..state import configure_compile_cache

    configure_compile_cache()
    return args.func(args) or 0


if __name__ == "__main__":
    sys.exit(main())
