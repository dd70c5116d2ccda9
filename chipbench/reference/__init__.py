"""Plain references of the models the benchmark trains, one module each.

A configuration file (``chipbench/configs/<name>.json``) names its module
under ``"reference"``; ``load`` imports ``chipbench.reference.<module>``.
The runner, ``control.py`` and ``metrics/train_mfu.py`` reach a model only
through that module, so a new architecture needs a new module here and
nothing else of the harness. Every module keeps this contract:

- ``Arch.from_config(cfg_file)``: the model's sizes from the file.
- ``program_config(cfg_file)``: the program's configuration of the file,
  the registry entry (``cfg_file["registry_id"]``) cut to the file's sizes,
  after checking every width and flag the reference depends on against it.
  It raises ``ValueError`` naming the fields that disagree. It is the one
  function that reads the program; nothing else here imports ``src/``.
- ``init_weights(arch, key)``: the seed's float32 weights, named and nested
  as the program names its parameters. A leaf may be shorter than the
  program's along the vocabulary axis alone (the program pads it); the
  runner pads it with zero rows.
- ``follow(arch, optim, seed, batches, devices, prec="f32")``: the reference
  trained from ``init_weights`` of ``common.seed_key(seed)`` over
  ``batches``, a list of (tokens, labels) each [blocks, rows, S]; returns
  ``{"loss": [...], "grad": [...], "delta": [...]}``, each step's loss, the
  first clipped gradient's norm per leaf, and each leaf's change after the
  last step. ``prec="fp8"`` is the control.
- ``delta_norms(w, arch, seed)``: each leaf's change from the seed's
  weights, for a program's (padded) weights ``w``.
- ``train_flops_per_token(cfg_file, seq_len)``: model FLOPs of one training
  token at that context, forward and backward, no recomputation.

``common`` holds what the decoder family shares.
"""

from __future__ import annotations

import importlib


def load(cfg_file: dict):
    """The reference module that the configuration file names."""
    who = cfg_file.get("registry_id", "a configuration file")
    name = cfg_file.get("reference")
    if not name:
        raise ValueError(f"{who}: the configuration file names no 'reference' "
                         f"module of chipbench/reference/")
    full = f"{__name__}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"{who}: reference {name!r} is no module of "
                         f"chipbench/reference/") from None
