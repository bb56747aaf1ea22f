#!/usr/bin/env python
"""Lint a captured SPMD plan's in/out specs and donation state.

Input: the JSON produced by `paddle_tpu.distributed.spmd.describe_plans()`
(a dict with "mesh" and "plans"; each plan lists its unique leaf classes
with shape/bytes/spec/slot_flagged/carried/donated — see
core/lazy.py describe_plans for the field contract).

Checks:
  * unsharded-but-shardable param/slot: an optimizer-managed buffer
    (slot_flagged) big enough to matter whose spec is fully replicated
    while some mesh axis (> 1 devices) divides one of its dims — HBM and
    bandwidth left on the table;
  * missing donation: a confirmed loop-carried optimizer slot the
    donating executable does not consume — the step allocates a fresh
    buffer for an in-place update. Stage-sharded ('pp' in the spec)
    leaves get the pipeline-specific wording: an undonated stage param
    costs a fresh copy of every stage's layer slice per microbatch
    round;
  * pipeline coverage (ISSUE 15): a captured pp_pipeline step on a mesh
    whose 'pp' axis has > 1 devices must carry at least one
    stage-sharded leaf — none means the trunk stacking silently
    replicated every stage's params (pp memory scaling lost);
  * expert coverage (ISSUE 20): a mesh whose 'ep' axis has > 1 devices
    must carry at least one expert-sharded ('ep' in spec) leaf across
    its lowered plans — none means every expert bank is replicated on
    every ep rank and the dispatch/combine all-to-all buys nothing;
  * serving KV replication (ISSUE 16): a serving engine dump
    (`engine.describe_sharding()`, detected by its "kv_pools" key) on
    an mp>1 mesh must head-shard each KV pool whose head count divides
    mp — replicated-but-shardable pools are the demotion the
    mesh-complete fast path removed.

Pure stdlib on purpose — no paddle_tpu / jax import, so it lints a
dumped JSON anywhere (CI box, laptop). The tests call `lint()` in-process
on the live description (tests/test_spmd.py, test_spmd_pp.py,
test_moe.py, test_paged_kernel.py); the CLI exits 1 when problems are
found.

Usage:
    python tools/sharding_lint.py plan.json
    python -c "import json, paddle_tpu.distributed.spmd as s; \\
               print(json.dumps(s.describe_plans()))" | \\
        python tools/sharding_lint.py -
"""
from __future__ import annotations

import argparse
import json
import sys

# below this, replicating a buffer is cheaper than the resharding traffic
MIN_SHARDABLE_BYTES = 1 << 16


def _mesh_axes(desc):
    mesh = desc.get("mesh") or {}
    return {k: int(v) for k, v in (mesh.get("axes") or {}).items()
            if int(v) > 1}


def _is_replicated(spec):
    return spec is None or spec == [] or (
        isinstance(spec, list) and all(s in (None, []) for s in spec))


def _shardable(leaf, axes):
    """Some mesh axis with >1 devices divides some dim of the leaf."""
    for d in leaf.get("shape", ()):
        for deg in axes.values():
            if d and d % deg == 0:
                return True
    return False


def _spec_has_axis(spec, axis):
    return isinstance(spec, list) and any(
        s == axis or (isinstance(s, list) and axis in s) for s in spec)


def lint_plan(plan, axes, min_bytes=MIN_SHARDABLE_BYTES):
    """Problem strings for one plan description (empty list = clean)."""
    problems = []
    if not plan.get("spmd"):
        return problems  # not lowered: nothing to check specs against
    is_pipeline = str(plan.get("first_op", "")).startswith("pp_pipeline")
    saw_stage_sharded = False
    for leaf in plan.get("leaves", ()):
        tag = (f"leaf class {leaf.get('class')} "
               f"{leaf.get('shape')}/{leaf.get('dtype')}")
        spec = leaf.get("spec")
        if spec == "opaque":
            continue  # GSPMD-inferred layout: can't judge from the spec
        stage_sharded = _spec_has_axis(spec, "pp")
        expert_sharded = _spec_has_axis(spec, "ep")
        saw_stage_sharded |= stage_sharded
        if leaf.get("slot_flagged") and axes and _is_replicated(spec) \
                and leaf.get("bytes", 0) >= min_bytes \
                and _shardable(leaf, axes):
            problems.append(
                f"{tag}: param/optimizer slot is replicated but a mesh "
                f"axis divides it — add a sharding_spec (or ZeRO "
                f"'sharding' annotation) so GSPMD shards it")
        if leaf.get("carried") and plan.get("donate_confirmed") \
                and not leaf.get("donated"):
            if stage_sharded:
                problems.append(
                    f"{tag}: stage-sharded (pp) param/slot is "
                    f"loop-carried but not donated — every step "
                    f"allocates a fresh copy of each stage's layer "
                    f"slice (check for a live Tensor holding the old "
                    f"stacked payload)")
            elif expert_sharded:
                problems.append(
                    f"{tag}: expert-sharded (ep) bank/slot is "
                    f"loop-carried but not donated — every step "
                    f"allocates a fresh copy of each ep rank's "
                    f"[E/ep] expert slice (check for a live Tensor "
                    f"holding the old bank payload)")
            else:
                problems.append(
                    f"{tag}: loop-carried optimizer slot is not donated "
                    f"— the captured step allocates a fresh buffer "
                    f"every iteration (check for a live Tensor holding "
                    f"the old payload)")
    if is_pipeline and axes.get("pp", 0) > 1 and not saw_stage_sharded:
        problems.append(
            "pipeline step has no stage-sharded leaf: the stacked trunk "
            "replicated over 'pp' instead of layer-sharding — per-stage "
            "param memory does not shrink with pp (check the stacked "
            "params' ('pp', ...) sharding_spec and dim-0 divisibility)")
    return problems


def lint(desc, min_bytes=MIN_SHARDABLE_BYTES):
    """All problem strings for a describe_plans() dict."""
    axes = _mesh_axes(desc)
    problems = []
    lowered = [p for p in desc.get("plans", ()) if p.get("spmd")]
    for i, plan in enumerate(desc.get("plans", ())):
        for p in lint_plan(plan, axes, min_bytes):
            problems.append(f"plan {i} ({plan.get('first_op', '?')}): {p}")
    # expert coverage (ISSUE 20): checked across plans (unlike pp there
    # is no marker op — any lowered plan may carry the expert banks)
    if axes.get("ep", 0) > 1 and lowered and not any(
            _spec_has_axis(leaf.get("spec"), "ep")
            for plan in lowered for leaf in plan.get("leaves", ())):
        problems.append(
            f"mesh has ep={axes['ep']} but no lowered plan carries an "
            f"expert-sharded ('ep') leaf — every expert bank is "
            f"replicated on every ep rank (check the banks' "
            f"('ep', ...) sharding_spec and num_experts % ep)")
    return problems


def lint_engine(desc, min_bytes=MIN_SHARDABLE_BYTES):
    """Problem strings for a serving engine's ``describe_sharding()``
    dict (ISSUE 16): a mesh engine whose per-layer KV pool is replicated
    while its HEADS (pools are [num_blocks, block_size, H*Dh], the
    record's "heads" is H; serving shards whole heads, never blocks or
    head_dim) divide the 'mp' axis left the exact demotion this PR
    removed on the table — every decode step gathers the full pool on
    every shard."""
    axes = _mesh_axes(desc)
    mp = axes.get("mp", 0)
    problems = []
    if mp <= 1:
        return problems  # single-chip (or no mesh): nothing to shard
    for pool in desc.get("kv_pools", ()):
        spec = pool.get("spec")
        if spec == "opaque":
            continue
        shape = pool.get("shape", ())
        tag = (f"kv pool layer {pool.get('layer')} "
               f"({pool.get('pool')}) {shape}/{pool.get('dtype')}")
        heads = pool.get("heads")
        if heads and heads % mp == 0 \
                and _is_replicated(spec) \
                and pool.get("bytes", 0) >= min_bytes:
            problems.append(
                f"{tag}: replicated on an mp={mp} mesh but its {heads} "
                f"heads divide mp — head-shard it "
                f"(P(None, None, 'mp')) so each shard holds "
                f"H/mp heads and the per-shard kernel route applies")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="describe_plans() JSON file, or - for "
                                 "stdin")
    ap.add_argument("--min-bytes", type=int, default=MIN_SHARDABLE_BYTES,
                    help="ignore replicated buffers smaller than this")
    args = ap.parse_args(argv)
    try:
        if args.path == "-":
            desc = json.load(sys.stdin)
        else:
            with open(args.path) as f:
                desc = json.load(f)
    except ValueError as e:
        print(f"{args.path}: not a JSON document: {e}", file=sys.stderr)
        return 2
    if "kv_pools" in desc:  # serving-engine describe_sharding() dump
        problems = lint_engine(desc, args.min_bytes)
        print(f"{len(desc.get('kv_pools', ()))} kv pool(s), "
              f"{len(problems)} problem(s)")
        for p in problems:
            print(f"  WARN {p}")
        return 1 if problems else 0
    problems = lint(desc, args.min_bytes)
    n_plans = len(desc.get("plans", ()))
    n_lowered = sum(1 for p in desc.get("plans", ()) if p.get("spmd"))
    print(f"{n_plans} plan(s), {n_lowered} SPMD-lowered, "
          f"{len(problems)} problem(s)")
    for p in problems:
        print(f"  WARN {p}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
