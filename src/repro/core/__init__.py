"""Synapse core: the paper's contribution as a composable JAX layer.

profile once (static watcher over compiled HLO, or runtime /proc watchers)
-> store (tagged, chunked, statistics over repeats)
-> emulate anywhere (resource atoms on any host/mesh)
-> predict TTC on hardware you don't have (roofline terms per sample).
"""
from repro.core.atoms import (CollectiveAtom, CollectiveQuant,  # noqa
                              CollectiveSpec, ComputeAtom, ComputeSpec,
                              MemoryAtom, MemorySpec, Plan, PlanCache,
                              StorageAtom, StorageSpec)
from repro.core.calibrate import HostCalibration, calibrate  # noqa
from repro.core.emulator import (EmulationReport, Emulator,  # noqa
                                 EmulatorSpec, FleetReport)
from repro.core.schedule import (BarrierStep, CompiledSchedule,  # noqa
                                 FusedSegment, SegmentRunner,
                                 compile_schedule, rehydrate_schedule)
from repro.core.hardware import (HOST_ARCHER_NODE, HOST_I7_M620,  # noqa
                                 HOST_STAMPEDE_NODE, TPU_V5E, TPU_V5E_2POD,
                                 TPU_V5E_POD, HardwareSpec, get_spec)
from repro.core.hlo_analysis import (HloCost, ModuleCost, analyze_hlo,  # noqa
                                     attribute_axes, sample_breakdown)
from repro.core.metrics import (ResourceVector, Sample,  # noqa
                                SynapseProfile)
from repro.core.predictor import (Prediction, RooflineTerms, compare,  # noqa
                                  from_dryrun_artifact, llm_request_resources,
                                  predict, predict_fleet, predict_resources,
                                  terms_for)
from repro.core.static_profiler import profile_compiled, profile_step  # noqa
from repro.core.store import ProfileStore  # noqa
from repro.core.watchers import (CPUWatcher, IOWatcher, MemWatcher,  # noqa
                                 RuntimeProfiler, WatcherBase, host_sysinfo)
