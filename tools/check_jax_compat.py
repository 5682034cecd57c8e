#!/usr/bin/env python3
"""Lint: version-fragile JAX spellings may appear only inside repro.compat.

Single source of truth for the rule — tests/test_compat.py imports this
module and the CI compat-lint job runs it as a script (stdlib only, no jax
needed). Import these symbols from ``repro.compat`` instead, so the next
JAX release that moves one is a one-file change.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Iterator, List

FORBIDDEN = [
    re.compile(r"from\s+jax\s+import\s+[^#\n]*\bshard_map\b"),
    re.compile(r"\bjax\.shard_map\b"),
    re.compile(r"from\s+jax\.experimental(\.shard_map)?\s+import\s+[^#\n]*\bshard_map\b"),
    re.compile(r"\bjax\.experimental\.shard_map\b"),
    re.compile(r"\bjax\.sharding\.AxisType\b"),
    re.compile(r"from\s+jax\.sharding\s+import\s+[^#\n]*\bAxisType\b"),
    re.compile(r"\bjax\.make_mesh\b"),
    re.compile(r"\bjax\.sharding\.get_abstract_mesh\b"),
    re.compile(r"\bjax\.lax\.axis_size\b"),
    re.compile(r"\bjax\.lax\.optimization_barrier\b"),
    re.compile(r"from\s+jax\.experimental(\.pallas)?\s+import\s+[^#\n]*\bpallas\b"),
    re.compile(r"from\s+jax\.experimental\.pallas\s+import\s"),
    re.compile(r"\bjax\.experimental\.pallas\b"),
]
SCAN_DIRS = ("src", "tests", "benchmarks", "examples")
EXEMPT = (
    os.path.join("src", "repro", "compat"),
    os.path.join("tests", "test_compat.py"),
    os.path.join("tools", "check_jax_compat.py"),
)


def _py_files(repo: str) -> Iterator[str]:
    for d in SCAN_DIRS:
        root = os.path.join(repo, d)
        if not os.path.isdir(root):
            continue
        for dirpath, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def find_offenders(repo: str) -> List[str]:
    offenders = []
    for path in _py_files(repo):
        rel = os.path.relpath(path, repo)
        if any(rel.startswith(e) for e in EXEMPT):
            continue
        with open(path, encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, 1):
                for pat in FORBIDDEN:
                    if pat.search(line):
                        offenders.append(f"{rel}:{lineno}: {line.strip()}")
                        break
    return offenders


# Any Pallas call site (pallas_call / pl.* entry points / pltpu.* scratch)
# must obtain its pallas modules from repro.compat — the entry-point location
# is version-sensitive.
#
# Note (kv-quant PR): the Proteus-quantized decode kernel
# (flash_decode_quant_fwd) and the block-sparse tile skip reuse the existing
# import_pallas()/pallas_vmem_scratch() entry points, and the deduped int4
# nibble pack/unpack helper (repro.kernels.common.pack_int4/unpack_int4) is
# pure jnp — no new version-sensitive Pallas accessor was needed. If a future
# kernel needs a NEW pl./pltpu. symbol, add it to repro.compat and extend
# _PALLAS_NAME below so this lint keeps recognising compat-imported sites.
#
# Note (paged-KV PR): the paged decode kernels build a scalar-prefetch grid
# spec (the page table rides as a prefetched scalar feeding kv BlockSpec
# index maps) — its class lives in the version-sensitive pltpu namespace, so
# it is obtained via compat's ``pallas_prefetch_grid_spec()`` accessor;
# naming ``PrefetchScalarGridSpec`` directly is flagged below.
_PALLAS_USE = re.compile(
    r"\bpallas_call\s*\(|\bpltpu\s*\.\s*\w+\s*\(|\bpl\s*\.\s*BlockSpec\s*\(|"
    r"\bPrefetchScalarGridSpec\s*\(")
# Two-part check so parenthesized multi-line imports pass: the file must
# import *something* from repro.compat AND name a pallas accessor somewhere.
_COMPAT_IMPORT = re.compile(r"from\s+repro\.compat[\w.]*\s+import\b")
_PALLAS_NAME = re.compile(
    r"\b(import_pallas|import_pallas_tpu|pallas_call|pallas_vmem_scratch|"
    r"pallas_prefetch_grid_spec)\b")


def find_pallas_offenders(repo: str) -> List[str]:
    """Files using Pallas entry points without importing them via compat."""
    offenders = []
    for path in _py_files(repo):
        rel = os.path.relpath(path, repo)
        if any(rel.startswith(e) for e in EXEMPT):
            continue
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        uses = [(lineno, line) for lineno, line in
                enumerate(text.splitlines(), 1) if _PALLAS_USE.search(line)]
        if uses and not (_COMPAT_IMPORT.search(text)
                         and _PALLAS_NAME.search(text)):
            lineno, line = uses[0]
            offenders.append(f"{rel}:{lineno}: {line.strip()} "
                             "(pallas entry points must come from repro.compat)")
    return offenders


# The fleet control plane (dispatcher + ServeFleet) must stay free of direct
# jax imports: routing/health/failover logic is pure host bookkeeping, and
# keeping jax out guarantees no version-sensitive symbol can leak in outside
# repro.compat (and that spawned mp workers pay the jax import only inside
# the worker engine, never for the facade). Engine/device work is reached
# through repro.launch.engine / repro.launch.serve instead.
_CONTROL_PLANE = (
    os.path.join("src", "repro", "distributed", "dispatcher.py"),
    os.path.join("src", "repro", "launch", "fleet.py"),
)
_JAX_IMPORT = re.compile(r"^\s*(import\s+jax\b|from\s+jax\b)")


def find_fleet_offenders(repo: str) -> List[str]:
    """Direct jax imports inside the fleet control-plane modules."""
    offenders = []
    for rel in _CONTROL_PLANE:
        path = os.path.join(repo, rel)
        if not os.path.isfile(path):
            continue
        with open(path, encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, 1):
                if _JAX_IMPORT.search(line):
                    offenders.append(
                        f"{rel}:{lineno}: {line.strip()} "
                        "(fleet control plane must not import jax directly)")
    return offenders


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = (find_offenders(repo) + find_pallas_offenders(repo)
                 + find_fleet_offenders(repo))
    if offenders:
        print("version-fragile JAX spellings outside repro.compat "
              "(import them from repro.compat instead):", file=sys.stderr)
        for line in offenders:
            print(f"  {line}", file=sys.stderr)
        return 1
    print(f"compat lint clean ({len(FORBIDDEN)} patterns + pallas-site rule "
          "+ fleet control-plane rule)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
