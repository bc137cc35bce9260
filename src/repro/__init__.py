"""repro — Firefly Monte Carlo (FlyMC) at pod scale, in JAX.

Layers:
  repro.api          — public sampling surface: (init, step) algorithms +
                       the device-resident multi-chain driver
  repro.core         — the paper's contribution: exact MCMC with data subsets
  repro.models       — GLM zoo (paper's experiments) + assigned LM architectures
  repro.data         — synthetic data generators + sharded global-array builders
  repro.optim        — AdamW/SGD/SGLD, gradient compression, microbatching
  repro.kernels      — Pallas TPU kernels for the compute hot spots
  repro.distributed  — mesh conventions, sharded FlyMC, parallelism rules
  repro.checkpoint   — atomic, elastic, multi-host checkpointing
  repro.launch       — mesh/dryrun/train/serve entry points
  repro.configs      — one config per assigned architecture + paper experiments
"""

__version__ = "1.1.0"
