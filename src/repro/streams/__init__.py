from repro.streams.app import (  # noqa: F401
    Edge,
    Grouping,
    InstanceGraph,
    Operator,
    StreamApp,
    parallelize,
    path_weights,
)
from repro.streams.faults import (  # noqa: F401
    FailureRecord,
    FaultAbort,
    FaultPlan,
    FaultSpec,
    InjectedFault,
)
from repro.streams.fleet import (  # noqa: F401
    CampaignResult,
    FleetRunner,
    FleetShape,
    pad_sim,
    simulate_many,
    stack_sims,
)
from repro.streams.load import LoadSchedule  # noqa: F401
from repro.streams.placement import STRATEGIES, round_robin, packed, traffic_aware  # noqa: F401
from repro.streams.scenarios import (  # noqa: F401
    Scenario,
    bench_fleet,
    campaign_fleet,
    capacity_sweep,
    compile_fleet,
    link_failure_sweep,
    random_app,
    random_scenarios,
    seed_fleet,
    time_varying_sweep,
)
from repro.streams.simulator import (  # noqa: F401
    CAMPAIGN_METRICS,
    CompiledSim,
    SimResult,
    compile_sim,
    metric_index,
    simulate,
)
from repro.streams.workloads import (  # noqa: F401
    PAPER_CAPS_MBPS,
    WORKLOADS,
    linkedin_tags,
    motivation_chain,
    nexmark_q5,
    trending_topics,
    trucking_iot,
)
