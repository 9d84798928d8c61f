"""The cases of the multi-device harness (tests/distributed_harness.py), as
plain data.

Each case is ``(case_id, check, params)``: ``check`` names a ``check_*``
function of the harness and ``params`` are its keyword arguments.  The
harness runs every case in one 8-device process and writes one record per
case; tests/test_distributed.py turns each case into one test.  This module
imports nothing from jax, so the test process can read the table without
setting a device count.
"""

#: 2-way decompositions (n_pf, n_pv, n_pr) checked against the oracle
DECOMPS_2WAY = [(1, 1, 1), (1, 2, 1), (1, 4, 1), (1, 8, 1), (2, 2, 1),
                (1, 2, 2), (2, 2, 2), (1, 4, 2), (4, 2, 1)]
#: 3-way decompositions (n_pf, n_pv, n_pr) of stage 0 of n_st=1
DECOMPS_3WAY = [(1, 1, 1), (1, 2, 1), (1, 4, 1), (2, 2, 1), (1, 2, 2),
                (1, 2, 4), (2, 2, 2)]


def _decomp(d) -> str:
    return "pf{}-pv{}-pr{}".format(*d)


def _cases():
    cases = []

    def add(check, *tags, **params):
        cases.append(("-".join([check, *map(str, tags)]), check, params))

    # 2-way: the reference decompositions, then every other implementation
    for d in DECOMPS_2WAY:
        add("2way", "xla", _decomp(d), impl="xla", decomp=d)
    for d in [(1, 2, 1), (1, 4, 1), (1, 2, 2)]:
        add("2way", "pallas", _decomp(d), impl="pallas", decomp=d)
    add("2way_packed")
    add("2way", "levels_xla", _decomp((1, 2, 1)), impl="levels_xla",
        levels=15, decomp=(1, 2, 1))
    for d in [(1, 2, 1), (1, 4, 1), (1, 2, 2), (2, 2, 1)]:
        add("2way", "levels", _decomp(d), impl="levels", levels=15, decomp=d)

    # 3-way: the reference decompositions, the fused kernels, the plane ring
    for d in DECOMPS_3WAY:
        add("3way", "xla", _decomp(d), impl="xla", decomp=d)
    add("3way", "pallas", _decomp((1, 2, 1)), impl="pallas", decomp=(1, 2, 1))
    for d in [(1, 2, 1), (2, 2, 1), (1, 2, 2), (1, 4, 1)]:
        add("3way", "levels", _decomp(d), impl="levels", levels=15, decomp=d)
    add("3way", "levels_xla", _decomp((2, 2, 1)), impl="levels_xla",
        levels=15, decomp=(2, 2, 1))
    add("3way", "levels-none", _decomp((1, 2, 1)), impl="levels", levels=15,
        encoding="none", decomp=(1, 2, 1))
    add("3way_staged")

    # the unified engine against the direct paths, and the CCC metric
    for d in [(1, 1, 1), (1, 4, 1), (2, 2, 2), (1, 2, 2)]:
        for way in (2, 3):
            add("engine_parity", f"{way}way", _decomp(d), way=way, decomp=d)
    for d in [(1, 1, 1), (1, 4, 1), (2, 2, 2)]:
        add("ccc", _decomp(d), decomp=d)
    add("ccc_pallas")

    # store-backed campaigns never run the host encoder
    for way in (2, 3):
        for d in [(1, 2, 1), (2, 2, 1), (1, 4, 1)]:
            add("plane_store", f"{way}way", _decomp(d), way=way, decomp=d)

    # streamed campaigns; a budget of 800 bytes forces 1-byte chunks
    for d, budget in [((1, 2, 1), 0), ((2, 2, 1), 0), ((1, 2, 2), 800)]:
        for way in (2, 3):
            add("streamed", f"{way}way", _decomp(d), f"budget{budget}",
                way=way, decomp=d, budget=budget)

    # binary data on the popcount bit-GEMM
    for d in [(1, 2, 1), (1, 2, 2), (2, 2, 1), (1, 4, 1)]:
        add("binary_popcount", _decomp(d), decomp=d)
    for impl in ("xla", "pallas", "levels", "levels_xla"):
        add("sorenson", impl, impl=impl)
    add("binary_store")
    add("binary_streamed")

    # border-block delta campaigns, in memory and streamed from a store
    for impl in ("xla", "levels"):
        for d in [(1, 1, 1), (1, 2, 2), (2, 2, 1), (1, 4, 2), (2, 2, 2)]:
            add("delta", impl, _decomp(d), impl=impl, decomp=d)
    for d, budget in [((1, 2, 1), 0), ((2, 2, 1), 0), ((1, 2, 2), 800)]:
        add("delta_streamed", _decomp(d), f"budget{budget}", decomp=d,
            budget=budget)
    return cases


CASES = _cases()
CASE_IDS = [case_id for case_id, _, _ in CASES]
assert len(set(CASE_IDS)) == len(CASE_IDS), "duplicate case ids"
