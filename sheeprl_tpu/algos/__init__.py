"""Algorithm package: importing it fires every @register_algorithm decorator
(the reference wires this in sheeprl/__init__.py:13-24). Imports are
ImportError-tolerant so an optional env extra never breaks the CLI
(reference cli.py:80-90)."""

_ALGO_MODULES = [
    "sheeprl_tpu.algos.ppo.ppo",
    "sheeprl_tpu.algos.ppo.ppo_decoupled",
    "sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent",
    "sheeprl_tpu.algos.ppo_bd.ppo_bd",
    "sheeprl_tpu.algos.sac.sac",
    "sheeprl_tpu.algos.sac.sac_decoupled",
    "sheeprl_tpu.algos.droq.droq",
    "sheeprl_tpu.algos.sac_ae.sac_ae",
    "sheeprl_tpu.algos.dreamer_v1.dreamer_v1",
    "sheeprl_tpu.algos.dreamer_v2.dreamer_v2",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3_decoupled",
    "sheeprl_tpu.algos.p2e_dv1.p2e_dv1",
    "sheeprl_tpu.algos.p2e_dv2.p2e_dv2",
    "sheeprl_tpu.serve.serve",
]

import importlib
import warnings

for _mod in _ALGO_MODULES:
    try:
        importlib.import_module(_mod)
    except ImportError as _e:  # optional env extra missing — skip, but say so
        warnings.warn(f"skipping algorithm module {_mod}: {_e}")
