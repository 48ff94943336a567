"""Campaign kinds. A traffic mix (``bench/traffic/<mix>.json``) names its
kind, and the kind's module ``bench/kinds/<kind>.py`` is found by that
name. A module drives one engine of the program and states what the
plain reference expects of it:

  KEYS       the traffic keys it reads besides ``COMMON``; a mix that
             sets any other key is refused, so nothing is ignored
  build(cell, params, engine_seed)
             the engine of one campaign, built the way users build it
  run(cell, engine)
             the campaign on the program; returns its history
  schedule(fleet, traffic, engine_seed, n_train)
             the reference's schedule of the same campaign, made with
             ``bench.reference`` alone and nothing of the program

``cell`` is the ``bench.campaign.Runner`` of the run: the configuration,
traffic, fleet, allocation problem, data and ``cell.model``, the
program's ``loss`` and ``eval`` functions for the configuration's learner
model (``bench/models/``).
A new kind is a new module here; a new mix of a kind is a data file.
"""

import importlib

#: keys every traffic mix has: the kind, the XLA program the per-layer
#: metrics read, and how many window campaigns the check compares
COMMON = ("kind", "program", "checked_campaigns")


def load(traffic: dict):
    """The kind module of ``traffic``, after checking its keys."""
    mod = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    extra = sorted(set(traffic) - set(COMMON) - set(mod.KEYS))
    missing = sorted((set(COMMON) | set(mod.KEYS)) - set(traffic))
    if extra or missing:
        raise ValueError(f"traffic of kind {traffic['kind']!r}: unknown keys "
                         f"{extra}, missing keys {missing}")
    return mod
