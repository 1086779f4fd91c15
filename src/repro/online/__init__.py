"""Online estimation subsystem (beyond-paper phase 5).

Closes the loop the paper leaves open: incremental conjugate posterior
updates (``repro.core.blr.update_task_batch``), an observation stream fed
back through ``LotaruEstimator.observe`` / ``LotaruML.observe``, and an
event-driven execution engine that interleaves run → observe → re-predict
→ re-schedule over grid-engine-style heterogeneous nodes, each online
tick one fused ``repro.core.tick.TickEngine`` dispatch.
"""
from .buffer import Observation, ObservationBuffer
from .executor import (CensoredRun, ExecutionTrace, OnlineExecutor, TaskRun,
                       fanout_chain_dag, run_static_and_online)
from .fleet import (FleetState, fleet_predict, fleet_slice, fleet_tick_step,
                    pad_obs, pad_state, shard_fleet, stack_states)

__all__ = ["Observation", "ObservationBuffer", "CensoredRun",
           "ExecutionTrace", "OnlineExecutor", "TaskRun",
           "fanout_chain_dag", "run_static_and_online", "FleetState",
           "fleet_predict", "fleet_slice", "fleet_tick_step", "pad_obs",
           "pad_state", "shard_fleet", "stack_states"]
