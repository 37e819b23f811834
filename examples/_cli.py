"""Shared CLI plumbing for the example scripts (the reference uses pico_args
subcommand CLIs; these mirror that shape: `./example check [ARGS]`)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stateright_tpu import WriteReporter  # noqa: E402
from stateright_tpu.actor import Network  # noqa: E402


def argv_subcommand():
    return sys.argv[1] if len(sys.argv) > 1 else None


def argv_int(pos: int, default: int) -> int:
    try:
        return int(sys.argv[pos])
    except (IndexError, ValueError):
        return default


def argv_str(pos: int, default: str) -> str:
    try:
        return sys.argv[pos]
    except IndexError:
        return default


def argv_network(pos: int, default: str = "unordered_nonduplicating") -> Network:
    try:
        return Network.from_str(sys.argv[pos])
    except IndexError:
        return Network.from_str(default)


def report(checker) -> None:
    checker.report(WriteReporter())


def thread_count() -> int:
    return os.cpu_count() or 1


def network_names() -> str:
    return " | ".join(Network.names())
