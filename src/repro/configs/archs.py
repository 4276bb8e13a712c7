"""Imports every per-architecture config module (side effect: registration)."""
from repro.configs import llama4_scout_17b_a16e  # noqa: F401
from repro.configs import chameleon_34b  # noqa: F401
from repro.configs import qwen1_5_110b  # noqa: F401
from repro.configs import seamless_m4t_large_v2  # noqa: F401
from repro.configs import mamba2_2_7b  # noqa: F401
from repro.configs import qwen1_5_4b  # noqa: F401
from repro.configs import dbrx_132b  # noqa: F401
from repro.configs import jamba_1_5_large_398b  # noqa: F401
from repro.configs import h2o_danube_1_8b  # noqa: F401
from repro.configs import nemotron_4_15b  # noqa: F401
from repro.configs import granite_4_0_h_micro  # noqa: F401

# id (with dashes/dots) -> module-registered config names are identical; this
# module exists so `get_config` can lazily trigger all registrations.
