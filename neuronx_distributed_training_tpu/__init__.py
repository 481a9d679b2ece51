"""neuronx_distributed_training_tpu — a TPU-native distributed LLM training framework.

A from-scratch JAX/XLA/Pallas re-design of the capability set of
aws-neuron/neuronx-distributed-training (the "reference"): YAML-driven pretraining,
SFT/LoRA and DPO/ORPO alignment for Llama/GPT/Mixtral-class models with
DP/TP/SP/PP/CP/EP parallelism, ZeRO-1 optimizer sharding, flash/ring attention,
mixed-precision regimes, sharded async checkpointing with auto-resume, and
throughput/MFU observability.

Architecture (reference layer map in SURVEY.md §1 → TPU-native):
  - one ``jax.sharding.Mesh`` with axes ``(data, pipe, context, model, expert)``
    replaces the NxD ``parallel_state`` machinery
  - GSPMD NamedSharding + ``shard_map`` collectives replace Neuron RT collectives
  - Pallas kernels replace the NKI flash/ring-attention kernels
  - the XLA persistent compilation cache replaces ``neuron_parallel_compile``
  - an explicit training loop replaces PyTorch-Lightning/NeMo
"""

import time as _time

#: the package's first import on ``time.perf_counter``'s clock, stamped before
#: anything else is imported: where the start-up timeline's
#: ``startup/before_program`` ends and ``startup/imports`` begins
#: (``telemetry/spans.py::STARTUP``, docs/observability.md "Start-up timeline")
_T_IMPORT = _time.perf_counter()

__version__ = "0.1.0"

from neuronx_distributed_training_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402,F401
from neuronx_distributed_training_tpu.utils.dtypes import DtypePolicy  # noqa: E402,F401
# creates the process's start-up timeline and arms the compile listener
# (telemetry/recompile.py) for everything that runs from here on
from neuronx_distributed_training_tpu.telemetry import spans as _spans  # noqa: E402

_spans.startup_add("startup/imports", _T_IMPORT)
