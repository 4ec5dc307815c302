"""``tools/mellum_control.py`` for the Command A+ cell (a TIED head under a
bias-free LayerNorm): the comparison that decides ``correct``, handed what
it has to refuse.

    chiprun -- python tools/command_a_control.py --seeds <n> ...
    chiprun -- python tools/command_a_control.py --seeds <n> \
        --program embed_init_std=1 attn_init_score_std=4   # other seeded scales

For each seed, through the cell's own ``check_reference``: the program as it
stands; the reference computed wholly in bfloat16, with only its router,
only its attention lowered; the float32 reference with a FAULT made
(``sequential_block``, ``rope_on_full``, ``no_window``, ...).  The loop, the
arguments and the output are ``tools/mellum_control.py``'s; the stand-in's
head is this model's.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mellum_control  # noqa: E402

CELL = "command-a-plus-05-2026.train_16k"
FAULTS = ("sequential_block", "rope_on_full", "no_window")


class StandIn(mellum_control.StandIn):
    """The reference where ``check_reference`` applies the program's model:
    its logits through the final bias-free LayerNorm and the TIED table, in
    the head's precision."""

    def head(self, params, hidden, dtype):
        logits = self.reference.layer_norm(
            hidden, params["ln_final"]["scale"],
            float(self.model["norm_eps"]), dtype, self.wrong,
        ) @ params["embed"]["embedding"].astype(dtype).T
        return logits * float(self.model.get("logit_scale") or 1.0)


def main(argv=None) -> int:
    return mellum_control.main(
        argv, cell=CELL, stand_in=StandIn, faults=FAULTS,
        out="command_a_control.json", doc=__doc__,
    )


if __name__ == "__main__":
    sys.exit(main())
