"""One module per architecture, found by the configuration.

A configuration file (``bench/configs/<config>.json``) may name its
architecture's module under the key ``"model"``; without it the module
is ``dense``. :func:`load` finds ``<name>.py`` in this directory (or in
the directory a run is given). The harness, the correctness check, the
control and the per-layer readers reach weights, reference, counts and
the program's ``ModelConfig`` only through that module; of the
configuration's ``arch`` they read only ``vocab``, ``lop_block`` and
``lop_keep`` themselves. A new architecture is a new module and a
configuration that names it: no file here or in the harness changes.

The interface a module gives (``arch`` is the configuration's ``arch``
dict, ``conf`` the whole configuration, ``seed`` the run's seed):

``model_config(conf)``
    The program's ``repro.configs.base.ModelConfig`` of the
    configuration.
``served_params(arch, seed)``
    The served weights in the program's serving format, made from the
    seed on the device in one jitted call.
``logits(arch, seed, tokens, n_prompt, *, length, k_keep, n_rows,
control=False)``
    The plain reference: a float32 ``numpy`` array [n_rows, vocab] of
    the logits at positions n_prompt - 1, n_prompt, ... of ``tokens``
    (prompt ‖ served tokens) padded to ``length`` positions, with the
    decoded positions attending to the ``k_keep`` blocks the LOP screen
    keeps. It imports nothing of the program and regenerates its
    weights from the seed in plain form. ``control=True``: the same in
    fp8 e4m3, the control (``bench/reference/model.py``).
``decode_token_ops(arch, live, k_keep, use_lop)``
    The operations one decoded token needs at ``live`` valid cache
    positions (``decode_mfu``).
``decode_attention_calls(arch, live, k_keep, use_lop)``
    ``[(ops, bytes, calls)]``: each shape of decode-attention kernel
    call one lane's token needs at ``live``, and how many times it runs
    (``decode_attention_roofline``).
``decode_projection_calls(arch, rows)``
    ``{kernel: [(ops, bytes), ...]}``: for each projection kernel of the
    kernel table, the shapes of its calls in one layer of a decode step
    of ``rows`` rows, in the order they repeat (``projections_roofline``
    divides the kernel's calls in a trace among them).
``KERNELS``
    Name patterns of the module's own kernels and programs, in the form
    of ``bench/kernels.json`` (``{"kernels": {name: [regex]},
    "programs": {name: [regex]}, "containers": [regex]}``, each part
    optional), which ``bench/trace.py`` merges into that common table;
    ``{}`` where the common table names them all.

Counts follow the rules of ``bench/cost.py``, whose ``qlinear`` and
``ffn`` give the shared ternary kernels.
"""

from __future__ import annotations

import importlib
import importlib.util
import sys
from pathlib import Path

DIR = Path(__file__).resolve().parent
DEFAULT = "dense"
INTERFACE = ("model_config", "served_params", "logits", "decode_token_ops",
             "decode_attention_calls", "decode_projection_calls", "KERNELS")


def name_of(conf: dict) -> str:
    """The module a configuration names (``dense`` where it names none)."""
    name = conf.get("model", DEFAULT)
    if not (isinstance(name, str) and name.isidentifier()):
        raise ValueError(f"configuration {conf.get('name')!r}: model "
                         f"{name!r} is not a module name")
    return name


def load(conf: dict, directory: Path | None = None):
    """The architecture module of configuration ``conf``, from this
    directory or from ``directory``."""
    name = name_of(conf)
    if directory is None or Path(directory).resolve() == DIR:
        mod = importlib.import_module(f"{__name__}.{name}")
    else:
        path = Path(directory).resolve() / f"{name}.py"
        key = f"bench_model:{path}"
        mod = sys.modules.get(key)
        if mod is None:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[key]
                raise
    missing = [a for a in INTERFACE if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"model module {name!r} lacks {missing}")
    return mod
