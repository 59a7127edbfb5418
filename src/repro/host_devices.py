"""Pre-jax-import virtual-device-count plumbing.

XLA locks the host device count at first jax init, so any CLI that offers
``--devices N`` must translate it into
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE importing
jax. This module therefore imports no jax and lives directly under the
``repro`` namespace package (no package ``__init__`` runs on import);
call :func:`force_host_device_count_from_argv` at the very top of an
entrypoint, ahead of the first jax import. The flag shapes only XLA's CPU
platform: on a TPU host the sharded engines see the real chips.
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Sequence


def parse_devices_argv(argv: Sequence[str]) -> Optional[str]:
    """Extract N from ``--devices N`` or ``--devices=N`` without argparse
    (argparse would need the full parser, which the entrypoints only build
    after jax is imported). Returns None when absent or valueless."""
    for i, tok in enumerate(argv):
        if tok == "--devices":
            return argv[i + 1] if i + 1 < len(argv) else None
        if tok.startswith("--devices="):
            return tok.split("=", 1)[1]
    return None


def force_host_device_count_from_argv(argv: Optional[Sequence[str]] = None):
    """Set the XLA host-device-count flag from ``--devices`` if present
    (appending to any existing XLA_FLAGS; an already-set device count
    wins). Malformed values are left for argparse to reject later."""
    d = parse_devices_argv(sys.argv if argv is None else argv)
    if d and d.isdigit() and int(d) > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={d}"
            ).strip()
