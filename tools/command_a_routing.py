"""``tools/mellum_routing.py`` on the Command A+ cell: how its seeded
sigmoid routers spread a 16,384-token sequence, layer by layer, at several
``embed_init_std`` (the busiest held expert against the mean, the pairs
routed here, the pairs past the row budget).

    chiprun -- python tools/command_a_routing.py --stds 0.02 1 2 \
        --program attn_init_score_std=4
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mellum_routing  # noqa: E402
from benchmark import build  # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    defaults = {
        "--seq": "16384", "--out": "command_a_routing.json",
        "--config": os.path.join(
            build.ROOT, "configs", "command-a-plus-05-2026.json"
        ),
    }
    for flag, value in defaults.items():
        if flag not in argv:
            argv += [flag, value]
    return mellum_routing.main(argv)


if __name__ == "__main__":
    sys.exit(main())
