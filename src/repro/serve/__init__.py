"""Simulation-as-a-service: the ensemble engine + session layer.

``ensemble`` vmaps the single-domain PIC step over a leading member axis —
one compiled program advances W independent parameter points per call.
``service`` puts a submit/step/poll session API with slot reuse on top
(modeled on inference serving engines: prefill/insert/generate over a fixed
batch of decode slots becomes init/insert/step over a fixed batch of
simulation slots).
"""

from repro.serve.ensemble import (EnsembleState, init_ensemble,
                                  make_ensemble_step, make_member_init,
                                  make_member_insert, make_member_release,
                                  member_view)
from repro.serve.service import SimService

__all__ = [
    "EnsembleState", "init_ensemble", "make_ensemble_step",
    "make_member_init", "make_member_insert", "make_member_release",
    "member_view", "SimService",
]
