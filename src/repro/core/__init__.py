"""repro.core — SA-Solver (NeurIPS 2023) and its substrate.

The paper's contribution, as a composable JAX module:

- unified plan/execute sampler registry (SA + all baselines)      samplers/
- per-step solver programs (variable order / mode / tau)          programs.py
- variance-controlled diffusion SDE family (tau schedules)        tau.py
- exact semi-linear solution machinery / Adams coefficients       coefficients.py
- SA-Predictor / SA-Corrector, Algorithm 1 (legacy shim)          solver.py
- noise schedules + timestep grids                                schedules.py
- baselines the paper compares against (legacy shims)             baselines.py
- analytic oracles + metrics for validation                       oracle.py, metrics.py

Sampling entry point: ``make_sampler(name, nfe=..., ...)`` — see
``repro.core.samplers`` and the top-level README.
"""

from .coefficients import SolverTables, build_tables, exp_monomial_integrals
from .denoiser import Denoiser, canonical_prediction, convert_prediction
from .oracle import GMM, gaussian_oracle, perturb_model
from .programs import (StepProgram, list_presets, parse_program,
                       program_preset)
from . import samplers
from .samplers import (
    Sampler,
    SamplerPlan,
    SamplerSpec,
    list_samplers,
    make_sampler,
    register_sampler,
)
from .schedules import (
    EDMSchedule,
    NoiseSchedule,
    RectifiedFlowSchedule,
    VESchedule,
    VPCosineSchedule,
    VPLinearSchedule,
    get_schedule,
    timestep_grid,
)
from .solver import SASolver, SASolverConfig, sample
from .tau import BandedTau, ConstantTau, DDIMEtaTau, TauSchedule

__all__ = [
    "samplers",
    "Denoiser",
    "canonical_prediction",
    "convert_prediction",
    "Sampler",
    "SamplerPlan",
    "SamplerSpec",
    "make_sampler",
    "register_sampler",
    "list_samplers",
    "SASolver",
    "SASolverConfig",
    "sample",
    "SolverTables",
    "build_tables",
    "exp_monomial_integrals",
    "NoiseSchedule",
    "VPLinearSchedule",
    "VPCosineSchedule",
    "VESchedule",
    "EDMSchedule",
    "RectifiedFlowSchedule",
    "get_schedule",
    "timestep_grid",
    "TauSchedule",
    "ConstantTau",
    "BandedTau",
    "DDIMEtaTau",
    "StepProgram",
    "program_preset",
    "list_presets",
    "parse_program",
    "GMM",
    "gaussian_oracle",
    "perturb_model",
]
