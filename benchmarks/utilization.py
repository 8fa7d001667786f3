"""Fig. 12: bottleneck-link utilization — App-aware must stay close to TCP
(paper: 99% / 97% vs TCP). The allocator's backfill pass (§VI-C) is what
keeps it work-conserving."""
from __future__ import annotations

from benchmarks.common import CAPS, emit, run_pair, singlehop_topo
from repro.compile_cache import setup_compile_cache
from repro.streams import trending_topics, trucking_iot


def run() -> list[dict]:
    rows = []
    for app_name, app_fn in (("TT", trending_topics), ("TI", trucking_iot)):
        for cap_name, cap in CAPS.items():
            tcp, aa = run_pair(app_fn, singlehop_topo(cap))
            rows.append({
                "name": f"fig12_utilization_{app_name}_{cap_name}",
                "tcp_util": round(tcp.bottleneck_utilization(), 3),
                "appaware_util": round(aa.bottleneck_utilization(), 3),
            })
    return rows


def main() -> None:
    emit(run(), "fig12")


if __name__ == "__main__":
    setup_compile_cache()
    main()
