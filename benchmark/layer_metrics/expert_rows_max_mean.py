"""Expert layer (ops/moe.py): the most rows any one expert took in a decode
step, mean over the window's boundaries that ran a decode chunk (ring
``expert_rows_max``, the chunk's mean over its steps and layers). With
``experts_hit_mean`` it says how uneven the routing was: live lanes x experts
a token / experts hit is the mean a hit expert took. A program whose ring
has no such field gives nothing."""


def read(run):
    top = [s["expert_rows_max"] for s in run.window_steps()
           if s["chunk"] > 0 and s.get("expert_rows_max")]
    return (sum(top) / len(top), len(top)) if top else None
