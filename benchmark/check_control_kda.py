"""The two readings ``solaropen2-docreport-steady``'s ``tolerance_std`` is set
between, on the chip, at the cell's own sizes: ``check_control_gdn.py`` (an
accepted benchmark file, whose default cell is Olmo-Hybrid's) run for this
cell. That script drives the engine's own programs (``_slot_prefill_jit``,
``_paged_insert_jit``, ``_lane_insert_jit``, ``_paged_decode_chunk_jit``) as
the configuration's family finds them: a two-part lane state beside ONE global
arena and no window ring, which is what ``kda_moe_lm`` keeps too (Kimi delta
attention's matrix states and convolution tails, one gated GQA layer's pages);
the expert layers' share rides in the params. For each seed the SOUND
program's slack against the plain reference, and for the first ``--fp8`` seeds
the slack against the reference with its weight matrices rounded to float8
(e4m3), which must read over the limit.

    python benchmark/check_control_kda.py --seeds 24 --fp8 4 [--first N]
        [--rehearsal] [--no-kernel]
"""

import os
import runpy
import sys

CELL = "solaropen2-docreport-steady"

if __name__ == "__main__":
    if "--cell" not in sys.argv:
        sys.argv += ["--cell", CELL]
    runpy.run_path(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "check_control_gdn.py"), run_name="__main__")
