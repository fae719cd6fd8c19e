"""The ``env_step`` kernel's share of its roofline in the simulation
cells: the least time the chip needs for the substeps the served lanes
ran, over the summed device time of the kernel's operations.

The count is the algorithm's: each stepped lane's own step cost in
substeps (the pool's ``cost_sum`` counter), not the masked loop's
``max_cost`` trips.  Per stepped lane the kernel reads its state (28
floats), action (8), cost and reward seed, and writes state and reward:
268 bytes.  Per lane-substep, ``SUBSTEP_OPS`` operations: XLA's count of
one unmasked substep of the dynamics (241 floating-point operations and
16 cosines) plus the 29 selects of the masked loop.  The bytes bound
it."""

KERNELS = ("env_multi_step",)
SUBSTEP_OPS = 286
LANE_BYTES = (28 + 8 + 1 + 1 + 28 + 1) * 4


def read(trace, counts):
    t = trace.kernel_s(*KERNELS)
    if t <= 0 or not counts.get("stepped"):
        return None
    p = counts["peaks"]
    least = max(counts["stepped"] * LANE_BYTES / p["hbm_bytes_per_s"],
                counts["substeps"] * SUBSTEP_OPS / p["bf16_flops_per_s"])
    return 100.0 * least / t
