"""Fig. 8 & Fig. 9: application throughput, TCP vs App-aware, at
10/15/20 Mbps — single-hop (up/downlink) and multi-hop (fat-tree internal)
bottlenecks. Paper: App-aware +15–31% (single-hop), +15–24% (multi-hop)."""
from __future__ import annotations

from benchmarks.common import (
    CAPS,
    emit,
    multihop_topo,
    run_pair,
    singlehop_topo,
)
from repro.compile_cache import setup_compile_cache
from repro.streams import trending_topics, trucking_iot


def run(figure: str = "fig8") -> list[dict]:
    topo_fn = singlehop_topo if figure == "fig8" else multihop_topo
    rows = []
    for app_name, app_fn in (("TT", trending_topics), ("TI", trucking_iot)):
        for cap_name, cap in CAPS.items():
            tcp, aa = run_pair(app_fn, topo_fn(cap))
            imp = (aa.throughput_tps / max(tcp.throughput_tps, 1e-9) - 1) * 100
            rows.append({
                "name": f"{figure}_throughput_{app_name}_{cap_name}",
                "tcp_tps": round(tcp.throughput_tps, 1),
                "appaware_tps": round(aa.throughput_tps, 1),
                "improvement_pct": round(imp, 1),
            })
    return rows


def main() -> None:
    for fig in ("fig8", "fig9"):
        emit(run(fig), fig)


if __name__ == "__main__":
    setup_compile_cache()
    main()
