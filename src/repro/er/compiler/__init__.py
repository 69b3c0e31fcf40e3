"""Unified match-job compiler: one plan → catalog → schedule → execute
pipeline (DESIGN.md §Compiler).

Every strategy — Basic / BlockSplit / PairRange self-joins, the Sorted
Neighborhood band, two-source R × S query jobs and the match_⊥ cross
jobs — flows through the same four stages:

  1. **Plan IR** (`ir.py`): the planner's output lowers into a
     :class:`MatchJob` — a flat table of corner-cut task rectangles over
     the blocked feature layout(s). `plan_to_job` is the only
     strategy-aware code in the whole execution stack.
  2. **Lowering** (`lower.py`): `lower(job) -> TileCatalog` tiles every
     task into MXU-aligned catalog entries — the single implementation
     behind what used to be six per-strategy `catalog_for_*` builders.
  3. **Scheduling** (`schedule.py`): an exact per-tile cost model (the
     live masked-pair count under the tile's predicates) feeds
     `core.assignment.greedy_lpt` to assign tiles → reducers → devices;
     `Schedule.stats()` reports the imbalance the paper optimizes.
  4. **Execution** (`execute.py`): one generic `execute(catalog,
     feats_*, mesh=...)` scores any catalog — single host, all-gather
     self-join, replicated-query cross join, or RepSN multi-hop halo
     exchange — through the fused kernel, replacing the per-strategy
     shard_map wrappers. A `comms=` policy (`comms.py`) swaps the flat
     all-gather for ring / hierarchical strip exchanges on the `data`
     axis, and `model_axis=` column-shards the features over a second
     mesh axis with in-scorer psum combination; every flow's
     bytes-received-per-device lands in `stage1_stats["interconnect"]`
     and on `Schedule.stats()`.

A fifth, optional layer wraps execution in a fault-tolerant supervisor
(`execute_supervised` + `faults.py`, DESIGN.md §Fault tolerance):
deterministic seeded fault injection (device kills, stragglers,
transient scorer errors, corrupted survivor output), per-device-shard
completion records, and tile-granular recovery — lost tiles are
re-scheduled over the shrunken healthy mask with bounded exponential
backoff, and survivors merge exactly-once at the match-set level.

A sixth closes the loop (`feedback.py`, DESIGN.md §Scheduling): an
EWMA model of measured seconds-per-live-pair per (device, tile class)
calibrates `schedule_tiles` and drives mid-stream work stealing in the
supervisor — slow devices' queued tiles are re-placed onto
faster-projected peers before the round ends.

`er/executor.py` and `er/distributed.py` keep their historical entry
points as thin shims over this package.
"""
from .ir import (  # noqa: F401
    A_TILE, B_TILE, R0, R1, C0, C1, TRI, LB_R, LB_C, UB_R, UB_C, BAND, RED,
    NCOLS,
    MatchJob,
    TileCatalog,
    cross_job,
    make_job,
    plan_to_job,
    sn_halo_job,
    task_row,
)
from .lower import (  # noqa: F401
    enumerate_catalog_pairs,
    enumerate_task_pairs,
    lower,
    pad_catalog,
    pad_tiles,
    task_tiles,
)
from .comms import (  # noqa: F401
    COMMS_POLICIES,
    CommsPlan,
    comms_volume,
    default_group,
    halo_bytes_per_device,
    halo_hop_rows,
    plan_comms,
    psum_bytes_per_device,
    rewrite_tiles_local,
)
from .schedule import (  # noqa: F401
    NoHealthyDevicesError,
    Schedule,
    apply_schedule,
    device_assignment,
    schedule_tiles,
    tile_costs,
    tiles_for_devices,
)
from .feedback import (  # noqa: F401
    N_TILE_CLASSES,
    TILE_CLASS_NAMES,
    EwmaCostModel,
    GeometryCostModel,
    tile_class,
)
from .tune import (  # noqa: F401
    GEOMETRY_LATTICE,
    GeometryScore,
    TuneReport,
    autotune,
    catalog_occupancy,
)
from .faults import (  # noqa: F401
    FAULT_KINDS,
    CallPlan,
    DeviceKilledError,
    FaultEvent,
    FaultInjector,
    FaultScript,
    TransientScorerError,
)
from .execute import (  # noqa: F401
    RecoveryFailedError,
    ShardRecord,
    SupervisedReport,
    execute,
    execute_supervised,
    make_scorer,
    match_catalog,
    score_catalog,
    shard_sane,
    stage1_stats,
    verify_pairs,
)
