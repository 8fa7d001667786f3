"""Operations and bytes one Alg. 1 interval (``allocate``) needs, counted
from the problem: the number of (flow, link) pairs on routes (nnz of R),
the flow count F and the link count L. Nothing here reads the dense
[F, L] shape a solver may use, so a sparse solver is held to the same
count and its share of the roofline cannot pass 100%."""
from __future__ import annotations


def allocate_work(nnz: int, F: int, L: int, backfill_iters: int = 8
                  ) -> dict:
    """Least work of one solve, each input read once and each output
    written once:

    * per flow: the uplink demand and drain rate of eqs. (3)/(4)
      (8 operations) and the final scale (1);
    * per pair: its share on the link (3), the min over the flow's links
      (1), the internal load and scale-down (3);
    * per link: the internal scale (3);
    * per backfill pass: the link load and the flow's least headroom
      (3 per pair), the headroom ratio (4 per link), the update (4 per
      flow).

    Bytes: R as one int32 link index per pair plus F + 1 int32 offsets,
    capacity and kind per link, the five float32 state fields per flow in
    and one rate per flow out."""
    ops = (9 * F + 7 * nnz + 3 * L
           + backfill_iters * (3 * nnz + 4 * L + 4 * F))
    nbytes = 4 * nnz + 4 * (F + 1) + 8 * L + 4 * 5 * F + 4 * F
    return {"ops": float(ops), "bytes": float(nbytes)}


def roofline_s(work: dict, peak: dict) -> tuple[float, str]:
    """Least time on the chip and which bound sets it ("bytes" or
    "ops"), against the published bf16 operation rate and HBM bandwidth."""
    t_ops = work["ops"] / peak["bf16_flop_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
