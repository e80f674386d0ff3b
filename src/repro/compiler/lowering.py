"""Lowering: plan IR -> executable closures over JAX/Pallas primitives.

``CompiledPlan`` binds a serializable ``Plan`` to one input graph and
evaluates nodes on demand with per-node memoisation:

* ``Contract``   -> ``CountingEngine.hom`` / ``hom_free_tensor`` (bucket
                    elimination einsums, f64, budget-chunked).  With a
                    mesh-bound engine the same nodes lower to collective
                    einsums over the row-sharded adjacency
                    (``distributed/contract``, route ``einsum-sharded``):
                    free cut tensors come back already sliced on cut
                    axis 0 and hand off to the sharded join tier without
                    a gather, and no unsharded n x n adjacency is ever
                    materialised;
* ``Intersect``  -> K3 and K4 by one exact int8 device pass over the
                    edges (route ``clique-device``), larger cliques by
                    degeneracy-ordered enumeration;
* ``CutJoin``    -> the fused Pallas kernel tier for |cut| <= 3: the
                    k-factor masked product-reduce (``kernels.ops.
                    cutjoin_reduce``) for |cut| <= 2, the tiled tri-join
                    (``cutjoin_reduce3``) for |cut| = 3 — axis-subset
                    factors broadcast per tile, pairwise-distinct mask
                    from tile iotas, so no O(n^|cut|) mask is ever
                    materialised — with chunked f32 tile partials summed
                    on the host in f64.  |cut| = 1 takes the vector fast
                    path.  Chunk sizes come from an exactness guard
                    (``cutjoin_exact_block``) fed by per-factor max
                    magnitudes cached on the plan: integer counts are
                    only routed to f32 chunks the bound proves exact.
                    The jitted XLA ``_join_reduce`` (dense factor stack
                    x explicit mask, f64, axis-subset factors broadcast
                    dense) remains the fallback for wider cuts /
                    over-bound magnitudes / ``cutjoin_kernel=False``,
                    and the interpret-mode oracle the kernel is tested
                    against;
* the combine ops run on host scalars.

Node values memoise per plan *and* feed the engine's hom memo, so
repeated queries against a compiled application never re-contract."""
from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.counting import CountingEngine
from repro.core.pattern import Pattern, clique
from repro.graph.storage import Graph
from repro.compiler.ir import (Contract, CutJoin, Intersect, LocalCount,
                               MobiusCombine, Plan, ShrinkageCorrect,
                               domain_keys, free_skeleton, is_local_output,
                               local_key, pattern_key)


@jax.jit
def _join_reduce(stack):
    """Π of the stacked factor tensors (leading axis), then full sum."""
    return jnp.sum(jnp.prod(stack, axis=0))


@functools.partial(jax.jit, static_argnames=("axis",))
def _join_keep(stack, axis):
    """Keep-axis XLA fallback/oracle: Π of stacked (n, n) factors, off-
    diagonal masked, summed over the non-kept axis (f64 under x64)."""
    prod = jnp.prod(stack, axis=0)
    off = 1.0 - jnp.eye(prod.shape[0], dtype=prod.dtype)
    return jnp.sum(prod * off, axis=1 - axis)


@functools.partial(jax.jit, static_argnames=("keep",))
def _join_keep3(stack, mask, keep):
    """Keep-axis |cut| = 3 XLA fallback/oracle: Π of stacked (n, n, n)
    factors under the dense pairwise-distinct mask, summed over the two
    non-kept axes (f64 under x64) — the tri-join kernel's bit-for-bit
    reference."""
    prod = jnp.prod(stack, axis=0) * mask
    return jnp.sum(prod, axis=tuple(a for a in range(3) if a != keep))


class CompiledPlan:
    """An executable application: one plan, one graph."""

    def __init__(self, plan: Plan, graph: Graph,
                 counter: Optional[CountingEngine] = None,
                 from_cache: bool = False,
                 budget: int = 1 << 27, cutjoin_kernel: bool = True,
                 mesh=None, count_store=None):
        self.plan = plan
        self.graph = graph
        # a default engine inherits the mesh so Contract nodes run their
        # hom contractions sharded too (a caller-supplied counter keeps
        # its own binding — pass mesh= to CountingEngine to shard it)
        self.counter = counter or CountingEngine(graph, budget=budget,
                                                 mesh=mesh)
        self.cutjoin_kernel = cutjoin_kernel
        self.from_cache = from_cache
        # execution mesh for the sharded tiers (a 1-D ("data",) jax
        # Mesh): joins block-shard over cut axis 0 (distributed/cutjoin)
        # and the default engine's hom contractions run as collective
        # einsums over the row-sharded adjacency (distributed/contract);
        # None keeps every route single-device
        self.mesh = mesh
        # morph count store (compiler.morph.CountStore): scalar hom
        # reads consult it before contracting (route "morph-derive")
        # and every count read harvests its exact scalars back into it
        self.count_store = count_store
        self._gsig: Optional[str] = None
        self._values: Dict[str, object] = {}
        self._masks: Dict[int, np.ndarray] = {}
        self._factors: Dict[tuple, np.ndarray] = {}
        self._factor_maxes: Dict[tuple, float] = {}
        self._precert: Optional[Dict[str, int]] = None
        # attach an ``obs.Tracer`` here to record per-node span trees on
        # every public read; None (the default) costs one is-None check
        # per node eval — nothing else
        self.tracer = None
        self.stats = obs.StatsView(
            "plan", keys=("node_evals", "node_hits", "exists_early_exits"))

    # -- tracing hooks -----------------------------------------------------------
    def _root(self, op: str, key: str):
        """Root "execute" span for one public read (no-op untraced).
        Node spans opened by the ``value`` recursion nest beneath it, so
        a trace's root coverage measures how much of the end-to-end read
        the per-node accounting explains."""
        tr = self.tracer
        if tr is None:
            return nullcontext()
        return tr.span(f"{op}:{key}", kind="execute", op=op)

    def _annotate(self, **attrs):
        """Attach attributes to the innermost open span (no-op untraced
        or outside any span — eval helpers are also called directly)."""
        tr = self.tracer
        if tr is not None:
            tr.annotate(**attrs)

    # -- morph store hooks -------------------------------------------------------
    def _store_hom(self, node_key: str):
        """Held scalar hom for one ``hom:`` node key, or None (no store
        attached / miss).  The graph signature is resolved lazily once."""
        if self.count_store is None:
            return None
        if self._gsig is None:
            from repro.compiler.cache import graph_signature
            self._gsig = graph_signature(self.graph)
        return self.count_store.get_key(self._gsig, node_key)

    def _harvest(self):
        if self.count_store is not None:
            self.count_store.harvest(self)

    # -- public API --------------------------------------------------------------
    def count(self, p: Pattern) -> float:
        """Edge-induced embedding count of one compiled pattern."""
        key = self.plan.output_for(p)
        with self._root("count", key):
            val = float(self.value(key))
        self._harvest()
        return val

    def counts(self) -> dict:
        """All compiled count outputs: canonical pattern key -> count
        (partial-embedding outputs are tensors — read them through
        ``local_counts``)."""
        with self._root("counts", "*"):
            out = {pk: float(self.value(nk))
                   for pk, nk in self.plan.outputs.items()
                   if not is_local_output(pk)}
        self._harvest()
        return out

    def has_local(self, p: Pattern, anchor: Optional[int] = None) -> bool:
        """True when the plan carries the requested partial-embedding
        output (compiled with ``local=True``; unanchored tensors need an
        eligible cutting set — cliques have none)."""
        return local_key(p, anchor) in self.plan.outputs

    def local_counts(self, p: Pattern,
                     anchor: Optional[int] = None) -> np.ndarray:
        """Partial-embedding counts of one pattern compiled with
        ``local=True``.

        ``anchor=None``: the full local tensor over the cutting set
        chosen for ``p.canonical()`` — axis j indexes the assignment of
        the j-th smallest cut vertex *of the canonical form*
        (``plan.meta["local_cuts"]`` records the cut; the key collapses
        isomorphic renumberings, so the shared answer is expressed in
        the one numbering every caller can reconstruct), entry e_c is
        the exact number of injective maps pinning the cut to e_c.
        ``anchor=v``: the (N,) vector of completion counts with pattern
        vertex v pinned per graph vertex — anchors in one automorphism
        orbit share their entry (``local_key`` collapses them).  Raises
        ``KeyError`` when the plan has no such output."""
        key = local_key(p, anchor)
        nk = self.plan.outputs.get(key)
        if nk is None:
            raise KeyError(
                f"plan has no partial-embedding output {key!r} "
                f"(compiled without local=True, or the pattern has no "
                f"eligible cutting set)")
        # a copy, not the memo: plans are memoised across serving steps,
        # so handing out the node-value array itself would let one
        # caller's in-place edit corrupt every later answer
        with self._root("local_counts", nk):
            return np.array(self.value(nk), np.float64)

    def exists(self, p: Pattern) -> bool:
        """Existence with early exit: on a local plan, factor tensors
        evaluate one subpattern at a time and an all-zero factor decides
        False before the join or any shrinkage correction runs (one
        subpattern with no embeddings means the whole pattern has none);
        otherwise any positive local entry — or, without a local output,
        the scalar count — decides."""
        nk = self.plan.outputs.get(local_key(p))
        node = self.plan.nodes.get(nk) if nk is not None else None
        with self._root("exists", nk or pattern_key(p)):
            if isinstance(node, LocalCount):
                for terms, ax in zip(node.factors, node.factor_axes()):
                    if not np.any(np.abs(self._combine(terms, len(ax)))
                                  > 0.5):
                        self.stats["exists_early_exits"] += 1
                        self._annotate(early_exit=True)
                        return False
                return bool(np.max(self.value(nk)) > 0.5)
            if nk is not None:
                return bool(np.max(np.asarray(self.value(nk))) > 0.5)
            return self.count(p) > 0.5

    def executable(self, p: Pattern):
        """Zero-arg closure for one pattern (plan handle for callers that
        dispatch queries later)."""
        key = self.plan.output_for(p)
        return lambda: float(self.value(key))

    def domains(self, p: Pattern) -> dict:
        """FSM MINI domain vectors of one pattern compiled with
        ``domains=True``: canonical orbit-representative vertex -> (N,)
        array counting injective maps sending that vertex to each graph
        vertex.  Raises ``KeyError`` when the plan has no domain nodes
        for ``p``."""
        out = {}
        with self._root("domains", pattern_key(p)):
            for key in domain_keys(p):
                if key not in self.plan.nodes:
                    raise KeyError(f"plan has no domain node {key!r} "
                                   f"(compiled without domains=True?)")
                out[int(key.rsplit(":", 1)[1])] = \
                    np.asarray(self.value(key))
        return out

    def mini_support(self, p: Pattern) -> int:
        """MINI support = min over pattern vertices of the domain size;
        orbit representatives suffice (orbit members share domains)."""
        return min(int(np.count_nonzero(dom > 0.5))
                   for dom in self.domains(p).values())

    # -- evaluation --------------------------------------------------------------
    def value(self, key: str):
        if key in self._values:
            self.stats["node_hits"] += 1
            return self._values[key]
        node = self.plan.nodes[key]
        self.stats["node_evals"] += 1
        cut = getattr(node, "cut_size", None)
        tr = self.tracer
        if tr is None:
            # the default: the profiler's ``gpm.node`` span alone, which
            # a traced eval's node span opens as well
            stats = {} if cut is None else {"cut": cut}
            with obs.span("node", key=key, cls=type(node).__name__,
                          **stats):
                val = self._eval(node)
        else:
            # one span per node eval, nested by the recursion itself
            # (refs evaluated inside ``_eval`` open child spans; memo
            # hits open none — the trace tree is exactly the work done).
            # ``predicted`` pairs the APCT cost the model charged at
            # selection time for the drift report; the fence closes the
            # span only after JAX async dispatch has really finished.
            attrs = {"predicted":
                     self.plan.meta.get("node_costs", {}).get(key)}
            if cut is not None:
                attrs["cut_size"] = cut
            with tr.span(key, kind=type(node).__name__, **attrs):
                val = obs.fence(self._eval(node))
        self._values[key] = val
        return val

    def _eval(self, node):
        if isinstance(node, Contract):
            if not node.free:
                held = self._store_hom(node.key)
                if held is not None:
                    self._annotate(route="morph-derive")
                    return float(held)
            shards = self.counter.contract_shards()
            if node.free:
                # decode the marker-encoded pattern: strips cut-rank
                # markers, restores real vertex labels (label-masked
                # contraction on labelled patterns)
                if shards > 1:
                    self._annotate(route="einsum-sharded",
                                   adjacency="sharded", mesh_axes=["data"],
                                   num_shards=shards)
                else:
                    self._annotate(route="einsum-free")
                skel = free_skeleton(node.pattern)
                return self.counter.hom_free_tensor(skel, node.free,
                                                    order=node.order)
            if shards > 1:
                self._annotate(route="einsum-sharded", adjacency="sharded",
                               mesh_axes=["data"], num_shards=shards)
            else:
                self._annotate(route="einsum")
            return self.counter.hom(node.pattern, order=node.order or None)
        if isinstance(node, Intersect):
            held = self._store_hom(node.key)
            if held is not None:
                self._annotate(route="morph-derive")
                return float(held)
            self._annotate(route=self.counter.clique_route(node.k))
            return self.counter.hom(clique(node.k))
        if isinstance(node, MobiusCombine):
            self._annotate(route="host")
            acc = 0.0
            for coeff, ref in node.terms:
                acc += coeff * self.value(ref)
            return acc / node.divisor
        if isinstance(node, CutJoin):
            return self._eval_cutjoin(node)
        if isinstance(node, LocalCount):
            return self._eval_local(node)
        if isinstance(node, ShrinkageCorrect):
            self._annotate(route="host")
            acc = self.value(node.base)
            for mult, ref in node.corrections:
                acc -= mult * self.value(ref)
            return acc / node.divisor
        raise TypeError(type(node))

    def _combine(self, terms, ndim: int) -> np.ndarray:
        """One Möbius factor tensor Σ coeff · tensor(ref), f64 — treat
        the result as READ-ONLY.  Genuine combinations memoise by term
        tuple (CutJoin and LocalCount nodes over the same cut, and
        ``exists`` early-exit probes, share them); a single identity
        term returns the node value itself — duplicating every Contract
        tensor into a second (n,)*ndim array would roughly double a
        long-lived serving plan's steady-state memory.  Sharded Contract
        tensors (jax Arrays sliced over cut axis 0 — see
        ``CountingEngine.hom_free_tensor``) stay on device: combining in
        jnp keeps the slices where the sharded join tier reads them, so
        the factor handoff never gathers."""
        if len(terms) == 1 and terms[0][0] == 1.0:
            v = self.value(terms[0][1])
            if isinstance(v, jax.Array):
                return v
            return np.asarray(v, np.float64)
        key = (terms, ndim)
        M = self._factors.get(key)
        if M is None:
            vals = [(coeff, self.value(ref)) for coeff, ref in terms]
            with obs.span("combine", terms=len(terms)):
                if any(isinstance(v, jax.Array) for _, v in vals):
                    # from the first term, keeping its sharding: a zeros
                    # start would first be whole on one device
                    with jax.enable_x64():
                        M = None
                        for coeff, v in vals:
                            term = coeff * jnp.asarray(v, jnp.float64)
                            M = term if M is None else M + term
                else:
                    M = np.zeros((self.graph.n,) * ndim)
                    for coeff, v in vals:
                        M = M + coeff * np.asarray(v, np.float64)
            self._factors[key] = M
        return M

    def _factor_max(self, terms, ndim: int, M) -> float:
        """max|M| for the factor combined from ``terms``, memoised under
        the same key as ``_combine``: the ``exact_block`` guard needs
        every factor's max magnitude on every kernel execution, and
        re-scanning long-lived serving factors would force a full
        device→host reduction per query.  Sharded factors reduce on
        device (one scalar transfer, no tensor gather)."""
        key = (terms, ndim)
        v = self._factor_maxes.get(key)
        if v is None:
            if not np.size(M):
                v = 0.0
            elif isinstance(M, jax.Array):
                with jax.enable_x64():
                    v = float(jnp.max(jnp.abs(M)))
            else:
                v = float(np.abs(np.asarray(M)).max())
            self._factor_maxes[key] = v
        return v

    def _join_factors(self, node):
        """(factors, axes) of a CutJoin/LocalCount node: each factor
        combined over its *own* axis subset (axis-subset factors stay at
        their own size).  Max magnitudes are *not* scanned here — the
        exactness guard (``_guard_block``) only pays for them when no
        static certificate covers the node, and the XLA route never
        needs them at all."""
        axes = node.factor_axes()
        Ms = [self._combine(terms, len(ax))
              for terms, ax in zip(node.factors, axes)]
        return Ms, axes

    def _precertified(self) -> Dict[str, int]:
        """Statically certified ``exact_block`` chunks, computed once
        per compiled plan from the *bound graph* — never trusted from
        ``plan.meta`` (a corrupted cached certificate would silently
        break kernel exactness; recomputing from the graph the plan is
        actually bound to costs microseconds and is always sound)."""
        if self._precert is None:
            from repro import analysis
            self._precert = analysis.precertify(
                self.plan, analysis.GraphInfo.from_graph(self.graph))
        return self._precert

    def _guard_block(self, node, Ms, axes):
        """The ``exact_block`` guard for one join.  Precertified nodes
        trust the static certificate — no device→host factor scan on
        the serving path; everything else scans factor magnitudes under
        a traced ``guard-scan`` span, so the cost the certificate
        removes stays visible in traces."""
        from repro.kernels import ops
        static = self._precertified().get(node.key)
        if static is not None:
            block = ops.runtime_block(static)
            obs.counter("kernel.exact_block", outcome="precertified")
            self._annotate(exact_block=block, precertified=True)
            return block
        tr = self.tracer
        ctx = (tr.span(f"guard:{node.key}", kind="guard-scan")
               if tr is not None else obs.span("guard_scan", key=node.key))
        with ctx:
            maxes = [self._factor_max(terms, len(ax), M)
                     for terms, M, ax in zip(node.factors, Ms, axes)]
            block = ops.cutjoin_exact_block(Ms, maxes=maxes)
        self._annotate(exact_block=block)
        return block

    def _dense_expand(self, Ms, axes, k: int):
        """Broadcast axis-subset factors to the full (n,)*k cut grid —
        the XLA dense fallback/oracle only; the kernel tier never calls
        this.  Costing admits |cut| >= 3 joins by their *factor* sizes
        (pair-only formulations stay eligible where n^k doesn't fit),
        so the dense fallback must refuse rather than materialise the
        n^k stack + mask the budget never approved — ``PlanTooWide``
        sends callers down their legacy fallback path."""
        from repro.core.homomorphism import PlanTooWide
        n = self.graph.n
        if k >= 3 and n ** k > 4 * self.counter.budget:
            raise PlanTooWide(
                f"dense |cut| = {k} fallback would materialise "
                f"{n ** k:.2e}-element factors/mask beyond the cap "
                f"(kernel guard refused or cutjoin_kernel=False)")
        out = []
        for M, ax in zip(Ms, axes):
            if len(ax) == k:
                out.append(M)
                continue
            shape = tuple(n if a in ax else 1 for a in range(k))
            out.append(np.broadcast_to(np.asarray(M).reshape(shape),
                                       (n,) * k))
        return out

    def _shard_fallback(self, reason: str):
        """Count one sharded-tier fallback, split by phase: a fresh
        compile's plan evals and a cache-hit serve's re-lower each
        re-evaluate the same nodes, so one shared counter double-counted
        the same logical fallback — phase-keyed counters (mirroring the
        batcher's ``fallbacks_compile``/``fallbacks_execute``) keep the
        two populations separable in ``obs`` snapshots."""
        phase = "execute" if self.from_cache else "compile"
        obs.counter(f"cutjoin.shard_fallbacks_{phase}", reason=reason)
        self._annotate(shard_fallback=reason)

    def _mesh_shards(self) -> int:
        """Usable shard count for this plan's joins: 1 without a mesh
        (or a trivial one); a graph smaller than the mesh falls back to
        single-device — slicing fewer rows than devices would leave
        idle shards and an all-padding grid on some of them."""
        if self.mesh is None:
            return 1
        from repro.distributed import meshes
        d = meshes.num_shards(self.mesh)
        if d <= 1:
            return 1
        if self.graph.n < d:
            self._shard_fallback("small-n")
            return 1
        return d

    def _eval_cutjoin(self, node: CutJoin) -> float:
        Ms, axes = self._join_factors(node)
        self._annotate(factor_shapes=[list(np.shape(M)) for M in Ms])
        shards = self._mesh_shards()
        if self.cutjoin_kernel and node.cut_size <= 3:
            from repro.kernels import ops
            block = self._guard_block(node, Ms, axes)
            if block is not None:            # f32 chunks provably exact
                if shards > 1:
                    from repro.distributed import cutjoin as dcj
                    self._annotate(route="kernel-sharded",
                                   mesh_axes=["data"], num_shards=shards)
                    with obs.span("join", route="kernel-sharded"):
                        if node.cut_size <= 2:
                            return dcj.sharded_cutjoin(
                                Ms, mesh=self.mesh,
                                distinct=node.cut_size >= 2, block=block)
                        return dcj.sharded_cutjoin3(
                            Ms, axes, n=self.graph.n, mesh=self.mesh,
                            block=block)
                self._annotate(route="kernel")
                with obs.span("join", route="kernel"):
                    if node.cut_size <= 2:
                        return ops.cutjoin_reduce(
                            Ms, distinct=node.cut_size >= 2, block=block)
                    return ops.cutjoin_reduce3(Ms, axes, n=self.graph.n,
                                               block=block)
            # factor magnitudes exceed what chunked f32 can represent
            # exactly: fall through to the f64 XLA join
            obs.counter("cutjoin.kernel_fallbacks", cut=node.cut_size)
        if shards > 1 and node.cut_size <= 3:
            # guard refusal / cutjoin_kernel=False under a mesh: the f64
            # dense join still shards (pure XLA, no chunking, no guard),
            # its injectivity mask built inside each shard
            from repro.distributed import cutjoin as dcj
            with obs.span("expand", cut=node.cut_size):
                Ms = self._dense_expand(Ms, axes, node.cut_size)
            self._annotate(route="xla-sharded", mesh_axes=["data"],
                           num_shards=shards)
            with obs.span("join", route="xla-sharded"):
                return dcj.sharded_dense_join(Ms, node.cut_size,
                                              mesh=self.mesh)
        with obs.span("expand", cut=node.cut_size):
            Ms = self._dense_expand(Ms, axes, node.cut_size)
            if node.cut_size >= 2:           # injectivity of the cut tuple
                Ms.append(self._mask(node.cut_size))
        if shards > 1:
            self._shard_fallback("wide-cut")
        self._annotate(route="xla-dense")
        with obs.span("join", route="xla-dense"), jax.enable_x64():
            stack = jnp.stack([obs.upload(M, site="xla_factors")
                               for M in Ms])
            return float(obs.readback(_join_reduce(stack),
                                      site="xla_result"))

    def _eval_local(self, node: LocalCount) -> np.ndarray:
        """The decomposition join without the final reduce.  Reduce-free
        (keep == all axes): the factor product with the off-diagonal
        mask applied *after* subtracting corrections — anchored
        correction tensors only equal true pinned-injective counts at
        distinct pins, so diagonal entries are defined to zero by the
        mask, matching Σ L = inj exactly.  Keep-axis (|cut| = 2, one
        surviving axis): the Pallas keep-axis kernel when the exactness
        guard admits the factors, else the jitted f64 XLA mask-and-sum
        (also the kernel's bit-for-bit oracle); corrections are already
        vector-sized and subtract after the reduce."""
        Ms, axes = self._join_factors(node)
        self._annotate(factor_shapes=[list(np.shape(M)) for M in Ms])
        if node.cut_size == 1 or len(node.keep) == node.cut_size:
            self._annotate(route="dense-product")
            with obs.span("join", route="dense-product"):
                dense = self._dense_expand(Ms, axes, node.cut_size)
                out = np.array(dense[0], np.float64)
                for M in dense[1:]:
                    out *= M
            if node.corrections:
                out -= self._combine(node.corrections, len(node.keep))
            self._zero_collisions(out)       # injectivity of the cut tuple
            return out
        # keep-axis reduce: |cut| in {2, 3}, one surviving axis
        axis = node.keep[0]
        out = None
        shards = self._mesh_shards()
        if self.cutjoin_kernel:
            from repro.kernels import ops
            block = self._guard_block(node, Ms, axes)
            if block is not None and shards > 1:
                from repro.distributed import cutjoin as dcj
                self._annotate(route="kernel-sharded-keep",
                               mesh_axes=["data"], num_shards=shards)
                with obs.span("join", route="kernel-sharded-keep"):
                    if node.cut_size == 2:
                        out = dcj.sharded_cutjoin_keep(Ms, keep=axis,
                                                       mesh=self.mesh,
                                                       block=block)
                    else:
                        out = dcj.sharded_cutjoin3_keep(
                            Ms, axes, keep=axis, n=self.graph.n,
                            mesh=self.mesh, block=block)
            elif block is not None:          # f32 chunks provably exact
                self._annotate(route="kernel-keep")
                with obs.span("join", route="kernel-keep"):
                    if node.cut_size == 2:
                        out = ops.cutjoin_reduce_keep(Ms, keep=axis,
                                                      block=block)
                    else:
                        out = ops.cutjoin_reduce3_keep(Ms, axes, keep=axis,
                                                       n=self.graph.n,
                                                       block=block)
            else:
                obs.counter("cutjoin.kernel_fallbacks", cut=node.cut_size,
                            keep=True)
        if out is None and shards > 1:
            # guard refusal / cutjoin_kernel=False under a mesh: the f64
            # dense keep join still shards (pure XLA, no chunking, no
            # guard) — mirroring the scalar route's ``xla-sharded``
            from repro.distributed import cutjoin as dcj
            with obs.span("expand", cut=node.cut_size):
                dense = self._dense_expand(Ms, axes, node.cut_size)
            self._annotate(route="xla-sharded-keep", mesh_axes=["data"],
                           num_shards=shards)
            with obs.span("join", route="xla-sharded-keep"):
                out = dcj.sharded_dense_join_keep(dense, node.cut_size,
                                                  keep=axis, mesh=self.mesh)
        if out is None:
            self._annotate(route="xla-keep")
            with obs.span("expand", cut=node.cut_size):
                dense = self._dense_expand(Ms, axes, node.cut_size)
            with obs.span("join", route="xla-keep"), jax.enable_x64():
                stack = jnp.stack([obs.upload(M, site="xla_factors")
                                   for M in dense])
                if node.cut_size == 2:
                    res = _join_keep(stack, axis)
                else:
                    res = _join_keep3(
                        stack, obs.upload(self._mask(3), site="xla_factors"),
                        axis)
                out = np.asarray(obs.readback(res, site="xla_result"),
                                 np.float64)
        if node.corrections:
            out = out - self._combine(node.corrections, 1)
        return out

    def _zero_collisions(self, out: np.ndarray):
        """Zero every entry whose index tuple repeats a value — the cut
        injectivity mask applied in place to a reduce-free local tensor
        (ndim 2: the diagonal; ndim 3: the three pairwise-equal planes;
        ndim 1: nothing — a single cut vertex is always injective)."""
        if out.ndim == 1:
            return
        if out.ndim == 2:
            np.fill_diagonal(out, 0.0)
            return
        assert out.ndim == 3
        idx = np.arange(out.shape[0])
        out[idx, idx, :] = 0.0
        out[idx, :, idx] = 0.0
        out[:, idx, idx] = 0.0

    def _mask(self, k: int) -> np.ndarray:
        """Π_{a<b} [x_a != x_b] over a (n,)*k grid."""
        if k not in self._masks:
            n = self.graph.n
            mask = np.ones((n,) * k)
            off = 1.0 - np.eye(n)
            for a in range(k):
                for b in range(a + 1, k):
                    shape = [1] * k
                    shape[a] = shape[b] = n
                    mask = mask * off.reshape(shape)
            self._masks[k] = mask
        return self._masks[k]


def lower(plan: Plan, graph: Graph, *, counter=None, from_cache=False,
          budget: int = 1 << 27, cutjoin_kernel: bool = True,
          verify: bool = False, mesh=None,
          count_store=None) -> CompiledPlan:
    """Bind a plan to a graph.  ``verify=True`` runs the static
    verifier against this graph first and raises ``PlanVerifyError``
    instead of binding a malformed plan — for plans that arrived from
    outside ``compiler.compile`` (hand-built, deserialized, mutated),
    which already verifies what it commits.  ``mesh`` (a 1-D
    ``("data",)`` jax Mesh) routes guarded joins through the sharded
    tier — numerically identical, see ``distributed/cutjoin.py``.
    ``count_store`` (a ``compiler.morph.CountStore``) serves held scalar
    homs without contracting and harvests every count read back."""
    if verify:
        from repro import analysis
        analysis.verify(
            plan, graph_info=analysis.GraphInfo.from_graph(graph),
            budget=budget).raise_if_failed()
    return CompiledPlan(plan, graph, counter=counter, from_cache=from_cache,
                        budget=budget, cutjoin_kernel=cutjoin_kernel,
                        mesh=mesh, count_store=count_store)
