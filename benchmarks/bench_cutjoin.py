"""CutJoin execution tiers: Pallas masked-reduce kernels vs the XLA
dense-mask joins vs the legacy direct contraction path.

Three levels:

* primitive — synthetic integer cut tensors, |cut| in {1, 2, 3}, timing
  one join evaluation per tier (the mask the XLA tier needs is prebuilt
  and amortised for |cut| <= 2, which flatters it; the |cut| = 3 XLA
  join builds its O(n³) mask the way the lowered fallback does — that
  materialisation is precisely what the tri kernel avoids).  The tri
  regime times both factor mixes: pair-tensor-only (the axis-subset
  form, e.g. a 6-cycle over cut {0,2,4}) and genuinely 3-D factors
  (e.g. 5-clique minus an edge);
* end-to-end 2-cut — a decomposed tailed-triangle plan against an ER
  graph, timing a full compiled count with the kernel tier on/off, plus
  the legacy ``CountingEngine.edge_induced`` direct path;
* end-to-end 3-cut — 5-clique minus an edge (its only cutting set has
  three vertices): the committed tri-join plan with the kernel on vs
  the XLA dense-mask fallback vs the best plan ``max_cutjoin_cut=2``
  can offer (the dense Möbius route — no eligible narrow cut exists),
  vs the legacy direct engine.  Counts must agree bit-for-bit.

Run:  PYTHONPATH=src python -m benchmarks.bench_cutjoin [--smoke]
``--smoke`` runs the tiny CI configuration; either way the rows land in
``benchmarks/results/BENCH_cutjoin.json`` for the trend renderer.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, timeit
from repro.graph import generators as gen
from repro.kernels import ops
from repro.compiler import frontend, lowering
from repro.core.pattern import Pattern

K5_MINUS_EDGE = Pattern(5, [(u, v) for u in range(5)
                            for v in range(u + 1, 5) if (u, v) != (3, 4)])


def _factors(n: int, cut: int, k: int, seed: int):
    rng = np.random.default_rng(seed)
    shape = (n,) * cut
    return [rng.integers(0, 8, size=shape).astype(np.float64)
            for _ in range(k)]


def bench_primitive(n: int, cut: int, k: int = 2, repeat: int = 0):
    repeat = repeat or (50 if cut == 1 else 20)
    Ms = _factors(n, cut, k, seed=n + cut)

    # the same routing the compiler uses: chunk size from the exactness
    # guard (per-chunk f32 partials provably exact on integer factors)
    block = ops.cutjoin_exact_block(Ms)
    assert block is not None

    dt, got_k = timeit(lambda: ops.cutjoin_reduce(Ms, distinct=cut >= 2,
                                                  block=block),
                       repeat=repeat, warmup=True)
    emit(f"cutjoin/kernel/n={n}/cut={cut}", dt * 1e6)

    mask = None
    if cut >= 2:
        mask = 1.0 - np.eye(n)              # prebuilt: amortises the XLA tier

    def xla_join():
        with jax.enable_x64():
            stack = [jnp.asarray(M) for M in Ms]
            if mask is not None:
                stack.append(jnp.asarray(mask))
            return float(lowering._join_reduce(jnp.stack(stack)))

    dt, got_x = timeit(xla_join, repeat=repeat, warmup=True)
    emit(f"cutjoin/xla/n={n}/cut={cut}", dt * 1e6)
    assert got_k == got_x, (n, cut, got_k, got_x)


def _tri_mask(n: int) -> np.ndarray:
    x = np.arange(n)
    return (((x[:, None, None] != x[None, :, None])
             & (x[:, None, None] != x[None, None, :])
             & (x[None, :, None] != x[None, None, :]))
            .astype(np.float64))


def bench_primitive3(n: int, mix: str, repeat: int = 5):
    """|cut| = 3 regime: the tri kernel (axis-subset factors broadcast
    per tile, in-kernel mask) vs the XLA dense path (factors expanded to
    n³, O(n³) mask materialised — what the lowered fallback pays)."""
    rng = np.random.default_rng(n)
    if mix == "pairs":                      # 6-cycle-style axis-subset join
        axes = [(0, 1), (1, 2), (0, 2)]
    else:                                   # K5-minus-edge-style 3-D factors
        axes = [(0, 1, 2), (0, 1, 2)]
    Ms = [rng.integers(0, 6, size=(n,) * len(ax)).astype(np.float64)
          for ax in axes]
    block = ops.cutjoin_exact_block(Ms)
    assert block is not None

    dt_k, got_k = timeit(lambda: ops.cutjoin_reduce3(Ms, axes, n=n,
                                                     block=block),
                         repeat=repeat, warmup=True)
    emit(f"cutjoin/kernel3/{mix}/n={n}", dt_k * 1e6)

    def xla_join():
        with jax.enable_x64():
            stack = [jnp.asarray(np.broadcast_to(
                M.reshape(tuple(n if a in ax else 1 for a in range(3))),
                (n, n, n))) for M, ax in zip(Ms, axes)]
            stack.append(jnp.asarray(_tri_mask(n)))   # the O(n³) mask
            return float(lowering._join_reduce(jnp.stack(stack)))

    dt_x, got_x = timeit(xla_join, repeat=max(repeat // 2, 1), warmup=True)
    emit(f"cutjoin/xla3/{mix}/n={n}", dt_x * 1e6,
         f"kernel_speedup={dt_x / max(dt_k, 1e-12):.1f}x")
    assert got_k == got_x, (n, mix, got_k, got_x)


def bench_end_to_end(n: int, repeat: int = 3):
    from repro.core.counting import CountingEngine
    from repro.core.pattern import cycle
    g = gen.erdos_renyi(n, 8.0, seed=11)
    p = cycle(4)                            # cut {0, 2}: a true 2-cut join
    cand = frontend.decomposed_candidate(p, frozenset({0, 2}), graph_n=g.n)
    plan = frontend.assemble([(p, cand)])

    join = next(node for node in plan.nodes.values()
                if type(node).__name__ == "CutJoin")
    eng = CountingEngine(g)
    cp = lowering.lower(plan, g, counter=eng, cutjoin_kernel=True)
    cp.count(p)                             # materialise factor tensors
    dt, got_k = timeit(lambda: cp._eval_cutjoin(join), repeat=repeat,
                       warmup=True)
    emit(f"cutjoin/e2e-kernel/n={n}", dt * 1e6)

    cx = lowering.lower(plan, g, counter=eng, cutjoin_kernel=False)
    cx.count(p)
    dt, got_x = timeit(lambda: cx._eval_cutjoin(join), repeat=repeat,
                       warmup=True)
    emit(f"cutjoin/e2e-xla/n={n}", dt * 1e6)
    assert got_k == got_x, (got_k, got_x)

    dt, got_d = timeit(lambda: CountingEngine(g).edge_induced(p), repeat=1,
                       warmup=False)
    emit(f"cutjoin/e2e-direct/n={n}", dt * 1e6)
    assert abs(got_d - cp.count(p)) < 1e-6, (got_d, cp.count(p))


def bench_end_to_end3(n: int, repeat: int = 2, direct: bool = True):
    """The acceptance regime: a pattern whose best (only) cutting set
    has |cut| = 3.  The compiler must commit the 3-cut plan, and the
    tri kernel must beat both the XLA dense-mask fallback and the best
    ``max_cutjoin_cut=2`` plan, counts bit-for-bit equal."""
    from repro import compiler
    from repro.core.counting import CountingEngine
    from repro.compiler.ir import CutJoin
    g = gen.erdos_renyi(n, 10.0, seed=7)
    p = K5_MINUS_EDGE

    eng = CountingEngine(g)
    cp = compiler.compile((p,), g, counter=eng, cache=False)
    join = next(node for node in cp.plan.nodes.values()
                if isinstance(node, CutJoin))
    assert join.cut_size == 3, "compiler did not commit the 3-cut plan"
    cp.count(p)                             # materialise factor tensors
    dt_k, got_k = timeit(lambda: cp._eval_cutjoin(join), repeat=repeat,
                         warmup=True)
    emit(f"cutjoin/e2e3-kernel/n={n}", dt_k * 1e6)

    cx = lowering.lower(cp.plan, g, counter=eng, cutjoin_kernel=False)
    cx.count(p)
    dt_x, got_x = timeit(lambda: cx._eval_cutjoin(join), repeat=1,
                         warmup=True)
    emit(f"cutjoin/e2e3-xla-densemask/n={n}", dt_x * 1e6,
         f"kernel_speedup={dt_x / max(dt_k, 1e-12):.1f}x")
    assert got_k == got_x, (got_k, got_x)

    # the best |cut| <= 2 the compiler can offer for this pattern is the
    # dense Möbius route (no eligible narrow cutting set exists): time
    # the full count on a fresh engine — same for the committed plan
    dt, cnt2 = timeit(
        lambda: compiler.compile((p,), g, counter=CountingEngine(g),
                                 cache=False,
                                 max_cutjoin_cut=2).count(p),
        repeat=1)
    emit(f"cutjoin/e2e3-forced-cut2/n={n}", dt * 1e6)
    dt, cnt3 = timeit(
        lambda: compiler.compile((p,), g, counter=CountingEngine(g),
                                 cache=False).count(p),
        repeat=1)
    emit(f"cutjoin/e2e3-tri-plan-full/n={n}", dt * 1e6)
    assert cnt3 == cnt2, (cnt3, cnt2)

    if direct:
        dt, got_d = timeit(lambda: CountingEngine(g).edge_induced(p),
                           repeat=1)
        emit(f"cutjoin/e2e3-direct/n={n}", dt * 1e6)
        assert got_d == cnt3, (got_d, cnt3)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration")
    ap.add_argument("--scale", default=None, help="legacy small-scale flag")
    args = ap.parse_args(argv)

    if args.smoke:
        sizes, tri_sizes = (256,), (128,)
    elif args.scale:
        sizes, tri_sizes = (512,), (256,)
    else:
        sizes, tri_sizes = (512, 1024), (256, 512)

    for n in sizes:
        for cut in (1, 2):
            bench_primitive(n, cut)
    for n in tri_sizes:
        for mix in ("pairs", "tri"):
            bench_primitive3(n, mix)
    bench_end_to_end(256 if args.smoke else 512)
    bench_end_to_end3(tri_sizes[-1], direct=not args.smoke)
    save_json("cutjoin")


if __name__ == "__main__":
    main()
