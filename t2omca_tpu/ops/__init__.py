"""Hot-path op reductions (query-slice / entity tables).

The Pallas fused-block kernel that used to live here
(``transformer_block.py`` + ``fast_agent.py``) was deleted in round 5:
it computed the FULL dense forward for every token, which the
query-slice reduction (token-0-only, K/V contracted away) and the
entity-table acting path strictly dominate on FLOPs.
"""

from .query_slice import (agent_forward_qslice, agent_forward_qslice_entity,
                          fold_agent_params, mixer_forward_qslice)

__all__ = ["agent_forward_qslice", "agent_forward_qslice_entity",
           "fold_agent_params", "mixer_forward_qslice"]
