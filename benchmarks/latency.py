"""Fig. 10 & Fig. 11: average end-to-end tuple latency, TCP vs App-aware.
Paper: App-aware −14–50% (TT single-hop), −6–17% (TI); multi-hop TI ≈ parity
(heavily congested internals)."""
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


def run(figure: str = "fig10") -> list[dict]:
    topo_fn = singlehop_topo if figure == "fig10" else multihop_topo
    rows = []
    for app_name, app_fn in (("TT", trending_topics), ("TI", trucking_iot)):
        for cap_name, cap in CAPS.items():
            tcp, aa = run_pair(app_fn, topo_fn(cap))
            imp = (1 - aa.avg_latency_s / max(tcp.avg_latency_s, 1e-9)) * 100
            rows.append({
                "name": f"{figure}_latency_{app_name}_{cap_name}",
                "tcp_latency_s": round(tcp.avg_latency_s, 2),
                "appaware_latency_s": round(aa.avg_latency_s, 2),
                "improvement_pct": round(imp, 1),
            })
    return rows


def main() -> None:
    for fig in ("fig10", "fig11"):
        emit(run(fig), fig)


if __name__ == "__main__":
    setup_compile_cache()
    main()
