"""The yardstick of each model family, found by the configuration's
``family`` (the ``model`` section of ``bench/configs/<config>.json``).

A family's module ``bench/families/<family>.py`` describes its model from
the configuration's sizes and the published equations, importing nothing
of the program, and gives:

* ``check(model)``: raises for a setting this description leaves out;
* ``param_shapes(model)``: one node's parameter shapes, as the nested dict
  the program holds them in (a node's own copy; the trainer stacks nodes
  first);
* ``init_leaf(path, shape, key)``: the initial f32 weights of the leaf at
  ``path`` (``/``-joined dict keys), drawn from ``key``;
* ``loss(params, tokens, labels, model, mm, valid)``: the mean next-token
  cross-entropy of one node's batch (B, S) over the tokens ``valid`` marks
  (all where it is None), with every matmul made by ``mm(spec, a, b)``, an
  ``einsum`` that the reference runs at ``Precision.HIGHEST`` or as the
  float8 control;
* ``flops_per_token(model, seq_len)``: forward and backward operations per
  token, recomputation excluded.

The rest of the yardstick (bucket plan, top-k, CHOCO mixing, comparison,
trace reduction) holds for every family.
"""
from __future__ import annotations

import importlib


def load(family: str):
    """The module of ``family``; raises naming the file to add where there
    is none."""
    name = f"{__name__}.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"the benchmark has no yardstick for model family "
                         f"{family!r}: add bench/families/{family}.py "
                         f"(see {__name__}'s docstring)") from None
