"""paddle_tpu.analysis — commit-time static analysis over the framework.

Rebuild of the reference's well-formedness tier: PIR's verify pass
(paddle/pir/src/core/ir_verify.cc, run after every pass pipeline) and the
YAML-driven consistency checks its codegen applies to the op library. On
the JAX rebuild the same guarantees are delivered by six CPU-only
analyzers that run at commit time:

- :mod:`program_verify` — well-formedness pass over the recorded
  ``static.Program`` IR (SSA/def-before-use, feed/fetch resolution,
  shape/dtype consistency vs ``ops/op_defs.py`` signatures, dead nodes,
  clone invariants).
- :mod:`trace_safety` — AST linter over the ``paddle_tpu/`` source tree
  flagging jit-unsafe host patterns inside traced regions (host syncs,
  tensor truthiness, clock/entropy reads, global mutation under trace).
- :mod:`registry_check` — promotes ``registry.alias_signature_report()``
  from advisory to enforced: every op row resolves, alias signatures
  bind, AMP lists stay disjoint, profiler tags stay valid, legacy
  ``op_compat`` names keep resolving.
- :mod:`jaxpr_audit` — trace-level verification of what the jit
  functionalizer hands to XLA: host callbacks, 64-bit dtype leaks,
  donation/output aliasing, dead values, guard-family coverage, and the
  recompilation audit (cache-key cardinality, static-key hygiene,
  bucket-ladder growth), and the eager kernel-cache audit (JX32x over
  ``core.kernel_cache.stats()``). Also ``CompiledFunction.audit()`` /
  ``audit_report()``.
- :mod:`spmd_check` — static mesh-axis resolution for collectives,
  shard_map/spmd regions and PartitionSpec annotations (SP4xx), with
  one-hop cross-file mesh-declaration resolution.
- :mod:`cost_model` — static FLOPs/bytes/collective-volume/peak-residency
  walker over the same retraced ClosedJaxprs (CM5xx), feeding
  ``CompiledFunction.cost()`` and the planner's jaxpr-backed HBM
  estimates.
- :mod:`telemetry_check` — the observability layer's own contract
  (OB6xx): no unclosed span at trace export, no duplicate metric
  registration, no blocking device sync inside a memory sampler.
- :mod:`comm_check` — the comm-efficient collective tier's contract
  (QZ8xx): quantized-allreduce accuracy/determinism gates, portable
  reshard route engagement, no mixed gradient-sync wire dtypes on one
  mesh axis.
- :mod:`fault_check` — the reliability layer's hygiene (FT9xx): no
  FaultInjector left armed outside a chaos run, no RetryPolicy with a
  dead deadline budget, no injection into a fault site whose
  release/cleanup path is undeclared.
- :mod:`concurrency_check` — the threaded runtime's lock discipline
  (CX10xx): no shared attribute mutated from two thread entry points
  without a lock, no static lock-order cycle, no blocking call under a
  held lock, no bare ``threading.Lock()`` outside the named-lock
  registry; plus the runtime lock-order witness
  (``observability/locks.py``, CX1004 inversions / CX1005 hold budget).
- :mod:`numerics_check` — the mixed-precision discipline (NM11xx): no
  dtype identity built by string surgery, no hardcoded fp32 cast inside
  AMP white-listed ops, no float64 into jnp calls; dtype-flow audit of
  retraced programs (narrow dot accumulation, oversized bf16
  reductions, int-to-narrow dequant epilogues), fp16-without-scaler and
  degenerate-quantizer object audits; plus the runtime NaN/Inf +
  dynamic-range witness (``observability/numerics.py``, NM1104/NM1105).
- :mod:`drift_check` — the program-drift gate (PD12xx): every
  representative program (TrainStep sharding tiers, serving batch
  ladder, paged-decode rung grid, qpsum oracle, reshard route) is
  retraced, canonically fingerprinted and compared against the
  committed ``programs.lock.json`` — new primitives, lost donation,
  dtype narrowing, rung-grid shrinkage and cost growth past the
  ``FLAGS_drift_max_*_ratio`` tolerances all gate. ``python -m
  tools.lint --update-lock`` regenerates the lock deterministically.

The ``# noqa: CODE — reason`` suppression grammar every source-scanning
family honours lives in :mod:`noqa` (one regex, one ``apply_noqa``).

One CLI drives them all: ``python -m tools.lint`` (exit 1 on any
error-severity finding, 2 on an analyzer crash; ``--json`` for
machine-readable output; ``--select``/``--ignore`` for code filters).
"""
from __future__ import annotations

from dataclasses import dataclass, field

__all__ = [
    "Finding",
    "audit_compiled_function",
    "audit_fault_injector",
    "audit_jaxpr",
    "audit_kernel_cache",
    "audit_numerics_witness",
    "audit_telemetry",
    "audit_witness",
    "check_concurrency_paths",
    "check_concurrency_source",
    "check_drift",
    "check_numerics_paths",
    "check_numerics_source",
    "check_cost",
    "check_fault_paths",
    "check_fault_source",
    "check_registry",
    "check_spmd_paths",
    "check_spmd_source",
    "check_telemetry_paths",
    "check_telemetry_source",
    "cost_compiled_function",
    "cost_jaxpr",
    "lint_paths",
    "lint_source",
    "verify_program",
]


@dataclass
class Finding:
    """One analyzer result. ``severity`` is 'error' (gates CI) or
    'warning' (reported, never gates). ``location`` is ``file:line`` for
    source findings, ``op[<index>]:<name>`` for program findings, and the
    op/alias name for registry findings."""

    analyzer: str   # 'program' | 'trace' | 'registry'
    code: str       # stable id, e.g. 'PV001' / 'TS101' / 'RC201'
    severity: str   # 'error' | 'warning'
    message: str
    location: str = ""
    extra: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {"analyzer": self.analyzer, "code": self.code,
             "severity": self.severity, "message": self.message,
             "location": self.location}
        if self.extra:
            d["extra"] = self.extra
        return d

    def __str__(self):
        loc = f"{self.location}: " if self.location else ""
        return f"{loc}{self.code} [{self.severity}] {self.message}"


def errors(findings) -> list:
    """The gating subset of a findings list."""
    return [f for f in findings if f.severity == "error"]


def iter_py_files(paths) -> list:
    """Every ``.py`` file under the given files/directories, sorted, with
    caches pruned. Shared by the source-scanning analyzers (trace, spmd)
    so they walk identically. A path that does not exist raises: a typo'd
    CI path must fail loudly, not lint zero files and report green."""
    import os

    files = []
    for path in paths:
        if os.path.isdir(path):
            for root, dirs, names in os.walk(path):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git", ".jax_cache")]
                files.extend(os.path.join(root, n)
                             for n in names if n.endswith(".py"))
        elif os.path.isfile(path) and path.endswith(".py"):
            files.append(path)
        else:
            raise FileNotFoundError(
                f"lint path '{path}' is not a directory or .py file")
    return sorted(files)


# Re-exported lazily-importable entry points (keep `import paddle_tpu`
# cheap: the analyzers pull ast/inspect only when actually called).
def verify_program(program, fetch_ids=None):
    from .program_verify import verify_program as _impl

    return _impl(program, fetch_ids=fetch_ids)


def lint_paths(paths):
    from .trace_safety import lint_paths as _impl

    return _impl(paths)


def lint_source(source, filename="<string>"):
    from .trace_safety import lint_source as _impl

    return _impl(source, filename)


def check_registry(**kwargs):
    from .registry_check import check_registry as _impl

    return _impl(**kwargs)


def audit_compiled_function(cf, **kwargs):
    from .jaxpr_audit import audit_compiled_function as _impl

    return _impl(cf, **kwargs)


def audit_jaxpr(closed_jaxpr, **kwargs):
    from .jaxpr_audit import audit_jaxpr as _impl

    return _impl(closed_jaxpr, **kwargs)


def audit_kernel_cache(stats=None, **kwargs):
    from .jaxpr_audit import audit_kernel_cache as _impl

    return _impl(stats, **kwargs)


def cost_jaxpr(closed_jaxpr, **kwargs):
    from .cost_model import cost_jaxpr as _impl

    return _impl(closed_jaxpr, **kwargs)


def cost_compiled_function(cf):
    from .cost_model import cost_compiled_function as _impl

    return _impl(cf)


def check_cost(report, **kwargs):
    from .cost_model import check_cost as _impl

    return _impl(report, **kwargs)


def check_spmd_paths(paths, **kwargs):
    from .spmd_check import check_paths as _impl

    return _impl(paths, **kwargs)


def audit_telemetry(tracer=None, registry=None, **kwargs):
    from .telemetry_check import audit_telemetry as _impl

    return _impl(tracer, registry, **kwargs)


def check_telemetry_paths(paths):
    from .telemetry_check import check_paths as _impl

    return _impl(paths)


def check_telemetry_source(source, filename="<string>"):
    from .telemetry_check import check_source as _impl

    return _impl(source, filename)


def check_spmd_source(source, filename="<string>", **kwargs):
    from .spmd_check import check_source as _impl

    return _impl(source, filename, **kwargs)


def check_fault_paths(paths):
    from .fault_check import check_paths as _impl

    return _impl(paths)


def check_fault_source(source, filename="<string>"):
    from .fault_check import check_source as _impl

    return _impl(source, filename)


def audit_fault_injector(injector="__live__"):
    from .fault_check import audit_injector as _impl

    return _impl(injector)


def check_concurrency_paths(paths):
    from .concurrency_check import check_paths as _impl

    return _impl(paths)


def check_concurrency_source(source, filename="<string>"):
    from .concurrency_check import check_source as _impl

    return _impl(source, filename)


def audit_witness():
    from .concurrency_check import audit_witness as _impl

    return _impl()


def check_numerics_paths(paths):
    from .numerics_check import check_paths as _impl

    return _impl(paths)


def check_numerics_source(source, filename="<string>"):
    from .numerics_check import check_source as _impl

    return _impl(source, filename)


def audit_numerics_witness():
    from .numerics_check import audit_witness as _impl

    return _impl()


def check_drift(live=None, lock_path=None):
    from .drift_check import check_drift as _impl

    return _impl(live=live, lock_path=lock_path)
