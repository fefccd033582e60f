# The paper's primary contribution: the hybrid training system —
# protocol, two-stage feature prefetching, DRM, performance model, and the
# hybrid (CPU + accelerators) trainer orchestration.
#
# The names below load on first use (PEP 562): ``repro.graph`` and
# ``repro.kernels`` emit ``core.spans`` spans, and ``core.hybrid`` imports
# them, so importing this package must not import ``hybrid`` eagerly.
import importlib

_MODULE_OF = {
    **dict.fromkeys(("Assignment", "DRMEngine", "KnobAutoTuner",
                     "KnobProposal", "StageTimes", "knob_neighbors"), "drm"),
    **dict.fromkeys(("PLATFORMS", "CalibratedKnobModel", "KnobBounds",
                     "KnobState", "PlatformSpec", "SignalSnapshot",
                     "StagePrediction", "WorkloadSpec", "calibrate_sampling",
                     "initial_task_mapping", "mteps", "predict",
                     "predict_epoch_time"), "perfmodel"),
    **dict.fromkeys(("PipelineItem", "PipelineStallError",
                     "PrefetchPipeline", "Stage"), "pipeline"),
    **dict.fromkeys(("Runtime", "Synchronizer", "TrainerHandle"),
                    "protocol"),
    **dict.fromkeys(("HybridConfig", "HybridGNNTrainer",
                     "IterationMetrics"), "hybrid"),
}

__all__ = list(_MODULE_OF)


def __getattr__(name):
    if name not in _MODULE_OF:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f".{_MODULE_OF[name]}",
                                            __name__), name)
    globals()[name] = value
    return value
