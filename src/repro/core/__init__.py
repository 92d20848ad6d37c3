# The paper's primary contribution: live DNN repartitioning with minimal
# edge service downtime (NEUKONFIG, IC2E'21).
from repro.core.controller import (POLICIES, CooldownPolicy, HysteresisPolicy,
                                   ImmediatePolicy, NeukonfigController,
                                   RepartitionEvent, RepartitionPolicy,
                                   SloAwarePolicy, get_policy,
                                   register_policy)
from repro.core.downtime import (SimResult, crosscheck_timeline,
                                 simulate_window, sweep_fps)
from repro.core.executor import (BackgroundBuildFailed, BuildCallbackFailed,
                                 BuildExecutor, BuildHandle, RetryPolicy)
from repro.core.faults import (FAULTS, FaultInjector, FaultPlan,
                               InjectedBuildFailure, available_faults, faults,
                               get_fault, register_fault)
from repro.core.hardware import CLOUD_SPEC, EDGE_SPEC, ICI_LINK_BW, TPU_V5E
from repro.core.network import (BandwidthTrace, CircuitBreaker, NetworkModel,
                                NetworkMonitor, PAPER_TRACE)
from repro.core.partitioner import (SplitDecision, latency_curve,
                                    optimal_split, should_repartition)
from repro.core.pipeline import EdgeCloudPipeline, RequestTiming
from repro.core.pool import (PipelinePool, PoolEntry, SwitchAborted,
                             SwitchAbortedWarning)
from repro.core.profiler import (ModelProfile, UnitProfile, profile_cnn,
                                 profile_transformer)
from repro.core.stages import StageRunner
from repro.core.state_handoff import (HandoffPlan, HandoffSplitClamped,
                                      per_layer_state_bytes, plan_handoff,
                                      range_state_bytes)
from repro.core.stateful import (DecodeSession, HandoffCorrupted,
                                 HandoffIntegrityWarning, HandoffReport,
                                 StatefulEdgeCloudPipeline,
                                 StatefulPipelinePool, StatefulStageRunner,
                                 make_stateful_manager, payload_checksum)
from repro.core.strategies import (Registry, SwitchReport, SwitchStrategy,
                                   apply_handoff, available_strategies,
                                   benchmark_specs, get_strategy,
                                   register_strategy, strategy_class,
                                   unregister_strategy)
from repro.core.switching import PipelineManager
