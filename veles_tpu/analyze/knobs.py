"""V-L05 — the knob registry: every ``root.common.*`` configuration
read a module performs must be DECLARED here.

The config tree auto-vivifies (a typo'd read silently returns an empty
node instead of failing), so the only line of defense against phantom
knobs is static: the lint pack walks every source file's AST, extracts
each ``root.common.…`` read chain (resolving inline ``.get("name")``
hops and stripping Config-method tails), and flags reads whose dotted
path no :data:`KNOB_REGISTRY` entry covers.  The same registry is the
single source for the docs knob-index table
(``python -m veles_tpu.analyze --knobs`` renders it; docs/knobs.md is
that output checked in).

Matching is bidirectional-prefix: a read of ``root.common.engine``
passes because registered leaves extend it, and a read of
``root.common.fleet.prefill_hosts`` passes because ``root.common
.fleet`` is registered as a group node (trailing ``.*`` marks groups
in the table).  A read that neither extends nor prefixes any entry is
a phantom knob — V-L05.
"""

import ast

RULES = {
    "V-L05": ("warning",
              "read of an undeclared root.common.* knob — the config "
              "tree auto-vivifies, so a typo'd path silently reads an "
              "empty node; declare every knob in analyze/knobs"
              ".KNOB_REGISTRY (the docs knob index is generated from "
              "it)"),
}

#: dotted path -> one-line description.  A key that other knobs extend
#: (``root.common.fleet``) declares the whole group.
KNOB_REGISTRY = {
    # engine — compilation / execution core
    "root.common.engine.backend":
        "device backend (auto | tpu | cpu | numpy); auto = the TPU or "
        "an error, the CPU only under JAX_PLATFORMS=cpu",
    "root.common.engine.interpret":
        "run units interpreted (NumpyDevice semantics) instead of jit",
    "root.common.engine.trace":
        "record per-dispatch prof ledger entries (on | off)",
    "root.common.engine.trace_capacity":
        "ring-buffer length of retained prof ledger entries",
    "root.common.engine.epoch_scan":
        "epoch-scan windowing mode (auto | on | off): lax.scan over "
        "whole-epoch minibatch windows",
    "root.common.engine.stitch":
        "stitched-segment fast path (on | off): fuse unit chains into "
        "one program per segment",
    "root.common.engine.health":
        "training-health telemetry (watch module) on | off",
    "root.common.engine.heartbeat_warn_ms":
        "scheduler heartbeat stall threshold before a warning",
    "root.common.engine.precision_level":
        "numeric strictness 0-2 (matmul precision / dtype discipline)",
    "root.common.engine.precision_type":
        "compute dtype family (float | bfloat16 mixed)",
    "root.common.engine.metrics_every":
        "steps between device-synced metric reads (host readback "
        "cadence)",
    "root.common.engine.loader":
        "loader staging mode (sync | async double-buffered)",
    "root.common.engine.recompile_sentinel":
        "fail the run on steady-state recompiles (count after warmup)",
    "root.common.engine.checkpoint":
        "snapshot cadence/policy for the snapshotter",
    "root.common.engine.kernels":
        "training-kernel backend (auto | xla | pallas): the fused "
        "backward-GD / flash-attention family, resolved at "
        "stage-build time (auto consults the autotune DB)",
    "root.common.engine.pallas_gemm":
        "use the Pallas GEMM kernel where shapes allow (on | off)",
    "root.common.engine.s2d_conv":
        "space-to-depth conv input transform (on | off)",
    "root.common.engine.seed":
        "global PRNG seed for prng.seed_all",
    "root.common.engine.thread_pool_workers":
        "background executor width for wants_thread units",
    "root.common.engine.mesh.axes":
        "named mesh axes table ({name: size}) for make_mesh",
    "root.common.engine.pod.topology":
        "pod mesh topology spelling (auto | N | DxM | "
        "axis=size[,axis=size] incl. pipeline/expert axes)",
    "root.common.engine.pod.preflight":
        "V-P02 pod preflight mode at install (off | warn | fail)",
    "root.common.engine.pod.param_rules":
        "pod param-sharding mode: auto = static planner picks "
        "replicated/fsdp/tp/pp/ep for the mesh at install()",
    "root.common.engine.pod.microbatches":
        "pipeline microbatches per step for the pipe axis "
        "(default: 4x the stage count)",
    # dirs — filesystem layout
    "root.common.dirs.datasets":
        "dataset root directory (MNIST et al. resolve under it)",
    "root.common.dirs.snapshots":
        "snapshot output directory",
    "root.common.dirs.results":
        "run results/export directory",
    "root.common.dirs.cache":
        "compiled-program / artifact cache directory",
    "root.common.dirs.user":
        "per-user scratch root the other dirs default under",
    # serve — online inference
    "root.common.serve.preflight":
        "V-S01 serving preflight mode at deploy (off | warn | fail)",
    "root.common.serve.quantize":
        "deploy-time weight quantization (off | int8)",
    "root.common.serve.infer_deadline_ms":
        "per-request inference deadline for the serving loop",
    # gen — generative/KV serving
    "root.common.gen.prefill_chunk":
        "chunked-prefill length (None = whole-prompt prefill)",
    "root.common.gen.kv":
        "KV-cache config group (mode contiguous | paged, block_size, "
        "num_blocks)",
    "root.common.gen.prefix_cache":
        "radix prefix cache over the paged pool (off | on): "
        "copy-on-write page sharing across shared-prefix admissions",
    "root.common.gen.speculative":
        "speculative decode proposer (off | ngram | a registered "
        "draft-model name); emitted tokens stay bitwise plain-decode",
    "root.common.gen.draft_k":
        "speculative draft span per slot per verify dispatch (1-7)",
    # obs / watch — observability
    "root.common.obs.blackbox_dir":
        "flight-recorder (blackbox) output directory",
    "root.common.obs.slo":
        "SLO thresholds group for the obs watchdog",
    "root.common.watch.endpoint":
        "ZMQ telemetry-bus endpoint the watch publisher binds",
    "root.common.watch":
        "training-health watch config group (thresholds, cadence)",
    # distributed serving / experiments
    "root.common.fleet":
        "disaggregated prefill/decode fleet config group (hosts, "
        "router, pools)",
    "root.common.chaos":
        "fault-injection (chaos) schedule group",
    "root.common.ensemble.train_ratio":
        "per-member train-subset fraction for ensemble runs",
    # UI / master-slave plumbing
    "root.common.graphics.port":
        "plotting server port",
    "root.common.graphics.multicast":
        "plotting event multicast group toggle/address",
    "root.common.web.host":
        "status web UI bind host",
    "root.common.web.port":
        "status web UI bind port",
    # misc
    "root.common.timings":
        "per-unit wall-clock timing printout toggle",
}

#: Config methods a read chain may end in — stripped before matching
#: (``root.common.engine.mesh.axes.to_dict()`` reads ``…mesh.axes``).
CONFIG_METHODS = frozenset((
    "get", "update", "to_dict", "print_", "protect", "copy"))


def chain_path(node):
    """AST expression → the dotted ``root.common.…`` path it reads, or
    ``None``.  Resolves inline ``.get("name")`` hops
    (``root.common.engine.get("pod")`` → ``root.common.engine.pod``)
    and cuts the chain at Config-method tails or any non-literal
    hop."""
    parts = []
    while True:
        if isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            func = node.func
            if (isinstance(func, ast.Attribute) and func.attr == "get"
                    and len(node.args) == 1 and not node.keywords
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                parts.append(node.args[0].value)
                node = func.value
            else:
                return None
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            break
        else:
            return None
    parts.reverse()
    if parts[:2] != ["root", "common"]:
        return None
    for i, part in enumerate(parts):
        if part in CONFIG_METHODS:
            parts = parts[:i]
            break
    if len(parts) <= 2:
        return None    # bare root.common — nothing to declare
    return ".".join(parts)


def iter_knob_reads(tree):
    """Yield ``(node, dotted_path)`` for every MAXIMAL
    ``root.common.…`` chain in ``tree`` (inner sub-chains of a longer
    chain are not re-reported)."""
    claimed = set()
    for node in ast.walk(tree):
        if id(node) in claimed:
            continue
        if not isinstance(node, (ast.Attribute, ast.Call)):
            continue
        path = chain_path(node)
        if path is None:
            continue
        for sub in ast.walk(node):
            claimed.add(id(sub))
        yield node, path


def declared(path):
    """Bidirectional-prefix match against :data:`KNOB_REGISTRY`."""
    for key in KNOB_REGISTRY:
        if path == key or key.startswith(path + ".") \
                or path.startswith(key + "."):
            return True
    return False


def render_knob_table():
    """The docs knob-index table (GitHub markdown), generated from the
    registry — ``python -m veles_tpu.analyze --knobs``."""
    keys = sorted(KNOB_REGISTRY)
    groups = {k for k in keys
              if any(o != k and o.startswith(k + ".") for o in keys)
              or k in ("root.common.fleet", "root.common.chaos",
                       "root.common.watch", "root.common.gen.kv",
                       "root.common.obs.slo",
                       "root.common.engine.mesh.axes")}
    lines = ["| knob | description |", "| --- | --- |"]
    for key in keys:
        shown = key + (".*" if key in groups else "")
        lines.append("| `%s` | %s |"
                     % (shown, KNOB_REGISTRY[key].replace("|", "/")))
    return "\n".join(lines)
