"""Declarative experiment API (see docs/experiment_api.md).

    from repro.api import Experiment, ExperimentSpec, TaskSpec, ...

    spec = ExperimentSpec(task=TaskSpec(name="blobs", n_samples=6000),
                          strategy=StrategySpec(name="feddf"))
    result = Experiment(spec).run()

Specs are JSON-round-trippable (``spec.to_json()`` / ``from_json``);
components resolve by name through the registries; ``Experiment.run``
serves both homogeneous and heterogeneous cohorts and
``Experiment.resume`` continues a checkpointed run.
"""
from repro.api.experiment import (Experiment, RoundEvent, RunResult,
                                  build_cohort, build_engine, build_mesh,
                                  build_source, build_splits,
                                  build_task_bundle, to_fl_config)
from repro.api.registries import (TaskBundle, available_models,
                                  available_quantizers, available_sources,
                                  available_tasks, default_prototype_ladder,
                                  get_model, get_quantizer, get_source,
                                  get_task, register_model,
                                  register_quantizer, register_source,
                                  register_task)
from repro.api.spec import (CohortSpec, DistSpec, DriverSpec, ExperimentSpec,
                            FaultSpec, FusionSpec,
                            ModelSpec, ObsSpec, PartitionSpec,
                            PopulationSpec, PrivacySpec, ShardingSpec,
                            SourceSpec, StrategySpec, TaskSpec,
                            TrafficSpec)

__all__ = [
    "Experiment", "RoundEvent", "RunResult",
    "ExperimentSpec", "TaskSpec", "PartitionSpec", "CohortSpec",
    "ModelSpec", "SourceSpec", "StrategySpec", "FusionSpec",
    "PrivacySpec", "ShardingSpec", "DriverSpec",
    "PopulationSpec", "TrafficSpec", "FaultSpec", "ObsSpec", "DistSpec",
    "TaskBundle", "register_task", "register_model", "register_source",
    "register_quantizer", "get_task", "get_model", "get_source",
    "get_quantizer", "available_tasks", "available_models",
    "available_sources", "available_quantizers",
    "default_prototype_ladder",
    "build_task_bundle", "build_splits", "build_cohort", "build_source",
    "build_mesh", "build_engine", "to_fl_config",
]
