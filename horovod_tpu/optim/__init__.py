from .distributed import (  # noqa: F401
    DistributedOptimizer,
    DistributedGradientTape,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_variables,
)
from . import profile_guided  # noqa: F401
FusionPlanSpec = profile_guided.FusionPlanSpec
ProfileGuidedTuner = profile_guided.ProfileGuidedTuner
plan_from_summary = profile_guided.plan_from_summary
plan_from_trace = profile_guided.plan_from_trace
warm_start_manager = profile_guided.warm_start_manager
