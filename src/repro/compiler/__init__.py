"""Pattern-to-plan compiler: DwarvesGraph's compilation tier.

The paper's headline design is *compilation-based* graph pattern mining:
generate candidate algorithms for every decomposition choice, cost them
with an accurate model, and ship the best one as an executable.  This
package is that tier, as a pipeline of five stages:

    pattern set ──frontend──► candidate plan IR fragments
                 (decomposition.candidates × homomorphism orders,
                  CutJoin/Shrinkage decomposition joins)
    fragments  ──costing───► winning joint plan
                 (APCT cost model, cross-pattern CSE: shared quotient
                  contractions scheduled once across the application)
    plan IR    ──lowering──► jitted executables
                 (CountingEngine einsum contractions, clique ordered
                  enumeration, Pallas triangle kernel)
    plan IR    ──cache─────► keyed by (canonical pattern set, graph
                  signature): compile once, execute many

Vertex labels are first-class through every stage: labelled patterns
generate the same candidate space (decomposition joins included — the
label mask lives inside each CutJoin factor, so the |cut| <= 3 Pallas
kernel tiers run unchanged), costing scales count bounds by label
selectivity, and lowering binds the pattern's label indices to the
bound graph's one-hot indicator rows at plan-bind time — one plan
serves any graph with a compatible label alphabet (out-of-alphabet
labels bind to the zero vector).

``compile(patterns, graph)`` is the single entry point; it returns a
``CompiledPlan`` whose ``.plan`` is the serializable IR (``to_json``)
and whose ``.count(p)`` / ``.counts()`` execute it.  With
``domains=True`` the plan additionally carries FSM MINI-domain nodes
(one vector per automorphism orbit) served by ``.domains(p)`` /
``.mini_support(p)`` — the level-wise FSM in ``core.fsm`` compiles each
candidate frontier jointly through this path.  ``MiningEngine``,
``launch.mine`` and ``serve.batching`` all route through here; the
legacy direct path in ``core.counting`` remains as the fallback.
"""
from __future__ import annotations

from typing import Iterable, Optional, Union

from repro import obs
from repro.core.pattern import Pattern
from repro.graph.storage import Graph
from repro.compiler import cache as _cache_mod
from repro.compiler import costing, frontend
from repro.compiler import morph as _morph
from repro.compiler.cache import PlanCache, config_compatible, plan_key
from repro.compiler.ir import Plan, local_key, pattern_key
from repro.compiler.lowering import CompiledPlan, lower
from repro.compiler.morph import CountStore, default_store

__all__ = ["compile", "Plan", "PlanCache", "CompiledPlan", "CountStore",
           "pattern_key", "plan_key", "local_key", "default_cache",
           "default_store", "config_compatible"]

_DEFAULT_CACHE = PlanCache()


def default_cache() -> PlanCache:
    """The process-wide plan cache used when ``compile(cache=None)``."""
    return _DEFAULT_CACHE


def _label_fracs(patterns, graph):
    """label -> vertex fraction of the bound graph, for selectivity
    pricing; None unless a labelled pattern meets a labelled graph."""
    if graph.labels is None or all(p.labels is None for p in patterns):
        return None
    import numpy as np
    counts = np.bincount(graph.labels, minlength=graph.num_labels)
    return {l: counts[l] / max(graph.n, 1) for l in range(graph.num_labels)}


def _add_local_outputs(plan, patterns, graph, apct, budget, counter,
                       label_fracs, max_cutjoin_cut, node_costs=None):
    """Partial-embedding outputs for every pattern: the unanchored local
    tensor (cheapest eligible cutting set, absent for cliques) plus one
    anchored vector per automorphism orbit (decomposed when a cut
    contains the orbit, flat Möbius otherwise).  Candidates are priced
    against the committed count plan's node pool, so local plans
    preferentially ride the cut tensors the counts already materialise
    — partial embeddings off the decomposition join, not a second
    pipeline."""
    import math as _math
    from repro.compiler.ir import local_key as _lk
    shared = {k: 0.0 for k in plan.nodes}
    local_cuts = {}

    def pick(cands):
        best, bc = None, _math.inf
        for cand in cands:
            c = costing.candidate_cost(cand, apct, graph.n, shared, budget,
                                       counter, label_fracs)
            if c < bc:
                best, bc = cand, c
        if best is None and cands:
            # every candidate prices infinite (genuinely too wide for
            # the budget — the width estimate now threads actual
            # free-axis participation, so this is rare): keep the last
            # candidate (anchored: the flat Möbius fallback) so the
            # output exists, but do NOT commit its nodes to the shared
            # pool — mirroring select_candidates, execution chunks or
            # raises PlanTooWide and callers fall back.
            best = cands[-1]
            for node in best.nodes:
                plan.add(node)
            return best
        if best is not None:
            costing.commit(best, apct, graph.n, shared, budget, counter,
                           label_fracs)
            for node in best.nodes:
                plan.add(node)
            if node_costs is not None:
                # setdefault: the seeded 0.0 of already-committed count
                # nodes must not overwrite their real selection cost
                for node in best.nodes:
                    node_costs.setdefault(node.key, shared[node.key])
        return best

    for p in patterns:
        # every local candidate — unanchored AND anchored — is built on
        # the CANONICAL form.  Unanchored: its key collapses isomorphic
        # renumberings, so the axes must refer to a numbering every
        # caller can reconstruct (canonical vertices).  Anchored: node
        # keys embed cut/keep signatures in local vertex ids under the
        # canonical ``pattern_key`` namespace, so instance-numbered
        # nodes could collide with canonical-numbered ones (same key,
        # different content — first-wins ``Plan.add`` would then serve
        # one anchor another anchor's vector).  One numbering per plan
        # makes equal keys mean equal content; anchored *values* are
        # numbering-invariant (completion counts per graph vertex), so
        # serving the canonical rep's vector for the instance anchor is
        # exact.
        pc = p.canonical()
        perm = p.canonical_perm()            # old (instance) -> canonical
        cand = pick(frontend.local_candidates(pc, graph_n=graph.n,
                                              budget=budget,
                                              max_cut=max_cutjoin_cut))
        if cand is not None:
            plan.set_local_output(pc, cand.out_key)
            local_cuts[_lk(pc)] = sorted(cand.cut)
        for orbit in p.vertex_orbits():
            cand = pick(frontend.local_candidates(
                pc, graph_n=graph.n, anchor=perm[orbit[0]], budget=budget,
                max_cut=max_cutjoin_cut))
            plan.set_local_output(p, cand.out_key, anchor=orbit[0])
            local_cuts[_lk(p, orbit[0])] = (sorted(cand.cut)
                                            if cand.cut else None)
    plan.meta["local_cuts"] = local_cuts


@obs.span("compile")
def compile(patterns: Union[Pattern, Iterable[Pattern]], graph: Graph, *,
            apct=None, counter=None, cache: Optional[PlanCache] = None,
            budget: int = 1 << 27, max_cutjoin_cut: int = 3,
            cutjoin_kernel: bool = True, domains: bool = False,
            local: bool = False, verify: bool = True, mesh=None,
            morph=False) -> CompiledPlan:
    """Compile a pattern (or application pattern set) for one graph.

    Cache hit: deserialise the stored plan and lower it (no search).
    Cache miss: build candidates per pattern, pick the joint winner under
    the shared-pool cost model, store the plan, lower it.

    ``max_cutjoin_cut=3`` (the default) emits decomposition-join
    candidates up to the tri-join kernel tier: |cut| = 3 joins use the
    axis-subset form (each factor spans only the cut vertices its
    subpattern touches) and the cost model's factor-tensor budget
    decides — per graph — whether a 3-D-factor formulation fits or the
    selection falls back to pair-only / |cut| <= 2 / dense candidates.

    ``cache=False`` disables caching; ``cache=None`` uses the process
    cache.  ``apct``/``counter`` let callers (e.g. ``MiningEngine``)
    share their profiling table and hom memo with the compiled plan —
    the counter's materialised hom/free-hom memos also feed costing, so
    re-compiles against a warm engine prefer decompositions whose cut
    tensors already exist.  ``cutjoin_kernel=False`` keeps CutJoin on the
    XLA ``_join_reduce`` path (the kernel tier's oracle).

    ``domains=True`` additionally emits FSM MINI-domain nodes per
    pattern (one free-hom Möbius combination per automorphism orbit),
    served by ``CompiledPlan.domains`` / ``.mini_support``; their
    free-hom contractions CSE-merge with decomposition-join factors.  A
    cached plan without domain nodes misses a ``domains=True`` lookup
    (and recompiles); the converse hit is fine — domain nodes are lazy.

    ``local=True`` additionally emits partial-embedding outputs (the
    paper's §5 API): per pattern, the unanchored local-count tensor over
    its cheapest eligible cutting set plus one anchored vector per
    automorphism orbit, served by ``CompiledPlan.local_counts`` /
    ``.exists``.  Local candidates are priced against the committed
    count plan, so they reuse its cut tensors; the same lazy-superset
    cache rule as ``domains`` applies.

    ``verify=True`` (the default) statically verifies every freshly
    assembled plan *before* it is cached or lowered
    (``repro.analysis.verify``): a frontend/costing bug that emits
    malformed IR raises ``PlanVerifyError`` at compile time instead of
    poisoning the cache, joins the degree bound precertifies skip the
    runtime ``exact_block`` guard scan (``plan.meta["precert"]``), and
    joins that could never take the kernel route are flagged to the
    metrics registry (``analysis.always_refused``).

    ``mesh`` (a 1-D ``("data",)`` jax Mesh, e.g. ``meshes.data_mesh()``)
    binds the plan to the sharded tier end to end: Contract nodes lower
    to collective einsums over the row-sharded adjacency
    (``distributed/contract.py`` — the n x n adjacency never
    materialises unsharded), guarded CutJoin/LocalCount nodes execute
    block-sharded over cut axis 0 (``distributed/cutjoin.py``), all
    bit-for-bit identical to single-device, and plan selection prices
    contractions and joins per-device with a collective surcharge
    (``costing``, ``devices=``).  The mesh does not enter the cache
    *key*, but its device count is part of the cross-config
    compatibility check on a hit (``cache.config_compatible``): a plan
    compiled against a mesh carries sharded route annotations and
    per-device cost estimates a meshless executor can't honour (and
    vice versa), so mismatched lookups recompile instead of serving it.

    ``morph`` turns the pattern-morphing count algebra on
    (``compiler.morph``): ``True`` uses the process-wide
    ``default_store()``, or pass a ``CountStore``.  Before searching,
    every query pattern is expanded over the store's held counts
    (inclusion–exclusion over the pattern lattice); when the whole
    query set closes algebraically the compiler skips candidate search
    entirely and serves a direct-shaped plan whose hom reads come back
    from the store (``plan.meta["morph"]``, route ``morph-derive``,
    ``obs`` counter ``morph.hits``) — zero contractions.  Partially
    closed queries still search, but held homs price at ~0
    (``costing.select_candidates(held=)``) and are served from the
    store at execution; fully-missing ones count
    ``morph.missing_compiles``.  Every count read of the returned plan
    harvests its exact scalars back into the store.  Morph-compiled
    plans are never written to the plan *cache* (their selection is
    store-biased; a later ``morph=False`` compile must behave exactly
    as if morphing never existed), and ``morph=False`` (the default)
    changes nothing anywhere.
    """
    if isinstance(patterns, Pattern):
        patterns = (patterns,)
    patterns = tuple(patterns)
    if not patterns:
        raise ValueError("compile() needs at least one pattern")

    if counter is not None:
        budget = counter.budget              # cost exactly what will execute
    use_cache = cache is not False
    if cache is None:
        cache = _DEFAULT_CACHE
    morph_store = None
    if morph is not False and morph is not None:
        morph_store = (morph if isinstance(morph, _morph.CountStore)
                       else _morph.default_store())
    from repro.distributed import meshes as _meshes
    mesh_devices = _meshes.num_shards(mesh)
    key = plan_key(patterns, graph)
    if use_cache:
        plan = cache.get(key)
        # a stored plan is only valid under the compile configuration
        # that selected it — budget, max_cutjoin_cut, and the execution
        # mesh's device count (see cache.config_compatible); a
        # cross-config hit recompiles instead of serving a plan the
        # executor must refuse or whose sharded routes it can't honour.
        # A domains=True request needs the domain nodes present; a plan
        # that has them serves domain-less requests unchanged.
        if plan is not None and config_compatible(
                plan, budget=budget, max_cutjoin_cut=max_cutjoin_cut,
                mesh_devices=mesh_devices):
            if (not domains or plan.meta.get("domains")) \
                    and (not local or plan.meta.get("local")):
                return lower(plan, graph, counter=counter, from_cache=True,
                             budget=budget, cutjoin_kernel=cutjoin_kernel,
                             mesh=mesh, count_store=morph_store)
            # config matches but the stored plan lacks a requested
            # flavor: recompile with the UNION of requested and stored
            # flags, so the overwrite supersets the entry instead of
            # ping-ponging between domains-only and local-only plans on
            # alternating request kinds
            domains = domains or bool(plan.meta.get("domains"))
            local = local or bool(plan.meta.get("local"))

    held = None
    if morph_store is not None:
        gsig = _cache_mod.graph_signature(graph)
        derived = [_morph.derive(p, morph_store, gsig) for p in patterns]
        if all(d.complete for d in derived) and not domains and not local:
            # the whole query set closes algebraically over held counts:
            # skip candidate search entirely and serve the direct-shaped
            # plan — lowering answers every hom node from the store
            # (route "morph-derive"), so no contraction ever runs
            for _ in patterns:
                obs.counter("morph.hits")
            plan = frontend.assemble(
                [(p, frontend.direct_candidate(p)) for p in patterns])
            plan.meta.update({
                "key": key, "budget": budget,
                "max_cutjoin_cut": max_cutjoin_cut,
                "mesh_devices": mesh_devices,
                "domains": False, "local": False,
                "estimated_cost": 0.0, "morph": True,
                "styles": {pattern_key(p): "morph" for p in patterns},
                "cuts": {pattern_key(p): None for p in patterns},
            })
            if verify:
                from repro import analysis
                ginfo = analysis.GraphInfo.from_graph(graph)
                plan.meta["graph_info"] = ginfo.to_dict()
                analysis.verify(plan, graph_info=ginfo,
                                budget=budget).raise_if_failed()
            return lower(plan, graph, counter=counter, from_cache=False,
                         budget=budget, cutjoin_kernel=cutjoin_kernel,
                         mesh=mesh, count_store=morph_store)
        for d in derived:
            if d.missing:
                obs.counter("morph.missing_compiles")
        # partial closure (or a domains/local request): fall through to
        # the search, but hand costing the held hom pool — held
        # contractions price at ~0 and execute from the store
        held = morph_store.held_hom_keys(gsig)

    if apct is None:
        from repro.core.apct import APCT
        with obs.span("apct"):
            apct = APCT(graph)
    with obs.span("candidates"):
        per_pattern = [(p, frontend.pattern_candidates(
            p, graph_n=graph.n, budget=budget,
            max_cutjoin_cut=max_cutjoin_cut)) for p in patterns]
    label_fracs = _label_fracs(patterns, graph)
    node_costs: dict = {}
    with obs.span("costing"):
        selections, total_cost = costing.select_candidates(
            per_pattern, apct, graph.n, budget, counter=counter,
            label_fracs=label_fracs, node_costs=node_costs,
            devices=mesh_devices, held=held)
        plan = frontend.assemble(selections)
    if domains:
        for p in patterns:
            for node in frontend.domain_candidate(p).nodes:
                plan.add(node)
    if local:
        _add_local_outputs(plan, patterns, graph, apct, budget, counter,
                           label_fracs, max_cutjoin_cut,
                           node_costs=node_costs)
    import math as _math
    plan.meta.update({
        "key": key,
        "budget": budget,
        "max_cutjoin_cut": max_cutjoin_cut,
        "mesh_devices": mesh_devices,
        "domains": domains,
        "local": local,
        "estimated_cost": total_cost,
        # per-node APCT predictions for committed nodes — the predicted
        # side of obs.drift's calibration report (traced executions pair
        # these with measured self times); uncommitted fallback nodes
        # and inf-priced entries carry no prediction
        "node_costs": {k: v for k, v in node_costs.items()
                       if k in plan.nodes and _math.isfinite(v)},
        "styles": {pattern_key(p): cand.style for p, cand in selections},
        "cuts": {pattern_key(p): sorted(cand.cut) if cand.cut else None
                 for p, cand in selections},
    })
    if verify:
        from repro import analysis
        with obs.span("verify"):
            ginfo = analysis.GraphInfo.from_graph(graph)
            # graph statistics ride in meta so cached plans re-verify
            # their budget pass without the graph; the precert copy is
            # advisory (observability/examples) — lowering recomputes
            # the certificate from the graph it actually binds, never
            # trusting cached meta
            plan.meta["graph_info"] = ginfo.to_dict()
            result = analysis.verify(plan, graph_info=ginfo, budget=budget)
        result.raise_if_failed()
        plan.meta["precert"] = dict(result.precert)
        for diag in result.warnings:
            if diag.code == "always-refused":
                obs.counter("analysis.always_refused")
    if use_cache and morph_store is None:
        # morph-biased selections never enter the shared plan cache: a
        # later morph=False compile must see PR-9-identical behaviour
        cache.put(key, plan)
    return lower(plan, graph, counter=counter, from_cache=False,
                 budget=budget, cutjoin_kernel=cutjoin_kernel, mesh=mesh,
                 count_store=morph_store)
