"""Plain reference of SmolLM-135M for the 8-client configuration.

The model, its weights, data, loss, control and FLOP counts are those of
``smollm-135m.py`` beside this file, which is loaded by path and
re-exported whole: only the configuration's JSON (8 clients, the client
axis over four chips) differs.
"""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "chipbench_config_smollm_135m_base",
    pathlib.Path(__file__).with_name("smollm-135m.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
globals().update({k: v for k, v in vars(_base).items()
                  if not k.startswith("__")})
