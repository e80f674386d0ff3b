"""Device idle seconds per job inside the Contract tier's own host work:
the innermost open span is ``gpm.contract`` (dispatching the einsums
of one hom contraction, up to the device's result) or
``gpm.adjacency`` (building the dense n x n adjacency on the host).
Booked instant by instant by ``idle_by_span.py``."""
LAYER = "Contract"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "contract_host_s")
