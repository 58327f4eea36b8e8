"""Central registry of every ``REPRO_*`` environment variable.

Every knob the repo reads from the environment is declared here once, with
its type, default, and consumers; ``get_env`` is the accessor call sites use.
The invariant linter's REP006 rule flags any ``REPRO_*`` read (direct
``os.environ`` or ``get_env``) whose name is missing from :data:`REGISTRY`,
and docs/envvars.md is generated from :func:`render_table` (pinned in sync
by tests/test_analysis.py) — so a new knob cannot ship undocumented.

Stdlib-only by construction: the linter imports this module to learn the
registered set, and the linter must work without jax installed.

Regenerate the docs table with::

    PYTHONPATH=src python -m repro.core.envvars > docs/envvars.md
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

__all__ = ["EnvVar", "REGISTRY", "get_env", "render_table"]


@dataclasses.dataclass(frozen=True)
class EnvVar:
    name: str
    kind: str                    # "choice" | "flag" | "int" | "path" | "spec"
    default: str                 # behavior when unset, as rendered in docs
    description: str
    consumers: Tuple[str, ...]   # modules that read it


REGISTRY: Tuple[EnvVar, ...] = (
    EnvVar(
        "REPRO_BENCH_MODE", "choice: fast / default / full", "default",
        "GA budget preset for benchmark runs (fast = tests/CI smoke, "
        "full = the paper's 100x100 sweep).",
        ("benchmarks.common",)),
    EnvVar(
        "REPRO_DEVICES", "spec: count / 'all' / i,j,...", "unset",
        "Device pool for campaign chunk sharding when the GAConfig does "
        "not name one (see repro.dist.pool.parse_device_spec); unset "
        "keeps jax default placement, byte-for-byte the pre-pool "
        "behavior.",
        ("repro.core.device_pool", "benchmarks.run")),
    EnvVar(
        "REPRO_FLEXION_BACKEND", "choice: numpy / jax", "auto",
        "Forces the MC flexion predicate backend; auto picks jax on an "
        "accelerator backend and numpy (the golden stream) on the CPU.",
        ("repro.core.flexion_batched",)),
    EnvVar(
        "REPRO_NO_PALLAS", "flag", "off",
        "Kernel-bridge autotuning falls back to the modeled objective "
        "instead of measured Pallas wall-clock (the autotune bench then "
        "refuses to run).",
        ("repro.core.kernel_bridge",)),
    EnvVar(
        "REPRO_SERVICE_CLIENTS", "int", "4",
        "Concurrent client count for the DSE service bench "
        "(`benchmarks.run --service N` sets it per pass).",
        ("benchmarks.service_bench", "benchmarks.run")),
    EnvVar(
        "REPRO_DRYRUN_JSONL", "path", "unset",
        "When set, the multi-pod roofline/bridge dry runs append each "
        "lowered program record to this JSONL file.",
        ("benchmarks.roofline", "benchmarks.bridge_validation")),
)

_BY_NAME = {v.name: v for v in REGISTRY}


def get_env(name: str, default: Optional[str] = None) -> Optional[str]:
    """The one accessor for ``REPRO_*`` knobs.  Unregistered names raise
    KeyError so a typo'd knob fails loudly at the read site instead of
    silently falling back to the default forever."""
    if name not in _BY_NAME:
        raise KeyError(
            f"{name!r} is not in repro.core.envvars.REGISTRY — register it "
            f"(name, kind, default, description, consumers) before reading")
    return os.environ.get(name, default)


def render_table() -> str:
    """docs/envvars.md, generated.  One row per registered variable."""
    lines = [
        "# Environment variables",
        "",
        "Generated from `repro.core.envvars.REGISTRY` — do not edit by "
        "hand.",
        "Regenerate: `PYTHONPATH=src python -m repro.core.envvars > "
        "docs/envvars.md`.",
        "The REP006 lint rule (docs/analysis.md) fails the build if a "
        "`REPRO_*` read exists without a registry entry, and "
        "tests/test_analysis.py fails if this file drifts from the "
        "registry.",
        "",
        "| Variable | Type | Default | Consumers | Description |",
        "|---|---|---|---|---|",
    ]
    for v in REGISTRY:
        consumers = ", ".join(f"`{c}`" for c in v.consumers)
        lines.append(f"| `{v.name}` | {v.kind} | {v.default} | "
                     f"{consumers} | {v.description} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    print(render_table(), end="")
