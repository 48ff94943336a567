"""Learner model families. A configuration names its family in
``cfg["model"]["family"]``, and the module ``bench/models/<family>.py``
is found by that name. The module gives the harness everything that
depends on the learner model, each function taking the whole
configuration ``cfg``:

  data(cfg, seed)
             ``(train_x, train_y, eval_x, eval_y)`` as NumPy arrays,
             made from ``seed``; the engines draw rows of ``train_x``
  init(cfg)  a ``seed -> params`` that makes the initial params on the
             device in one jitted call
  program(cfg)
             ``(loss_fn, eval_fn)``: the program's own functions that the
             engines take, ``loss_fn(params, {"x", "y", "mask"})`` and
             ``eval_fn(params, x, y)``; imports the program lazily
  time_terms(cfg)
             Eq. 5's ``(C_m, S_m, sample_bits)``: FLOPs per sample, the
             model's bits and the bits one sample ships
  flops_per_sample_step(cfg)
             the FLOPs of one sample's forward and backward pass, which
             ``mfu`` and ``bench.work.flops`` count
  least_bytes(cfg, rows)
             the HBM bytes no implementation of the campaign's schedule
             ``rows`` can avoid (``bench.work.least_seconds``)
  loss(params, x, y, m), accuracy(params, x, y)
             the plain reference in straightforward ``jax.numpy``:
             masked mean cross-entropy and the share of rows classified
             right. Matmuls run at HIGHEST precision on float32 params,
             and in bfloat16 where the control casts the params to it.
             This part imports nothing of the program.
             ``loss`` gets one learner's block, unbatched: ``x`` and ``y``
             of shape ``(rows, ...)`` and the mask ``m`` of shape
             ``(rows,)``, 1 on the learner's samples and 0 on the padding.
             The reference maps it over a group of learners with ``vmap``,
             or, where one learner is all that fits in the memory budget
             (``bench/reference.py``), calls it on that learner alone. It
             must stay the masked mean over the rows where m = 1, and it
             may skip rows whose mask is 0, such as a block of rows behind
             a ``lax.cond``: under ``vmap`` the cond becomes a select and
             computes both sides, on its own it skips.

A new family is a new module here, and its configuration a data file.
"""

import importlib

REQUIRED = ("data", "init", "program", "time_terms", "flops_per_sample_step",
            "least_bytes", "loss", "accuracy")


def load(cfg: dict):
    """The family module of ``cfg``, after checking that it defines every
    name in ``REQUIRED``."""
    family = cfg["model"]["family"]
    mod = importlib.import_module(f"bench.models.{family}")
    missing = [n for n in REQUIRED if not callable(getattr(mod, n, None))]
    if missing:
        raise ValueError(f"model family {family!r} does not define {missing}")
    return mod
