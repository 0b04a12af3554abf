"""Model step (models/generation.py): the lanes whose slice of the LANE STATE a
decode step read and wrote, mean over the window's boundaries that ran a
decode chunk (ring ``state_lanes`` where ``chunk > 0``), worked out on the
engine thread (``generation.state_write_lanes``). While a step updates the
state's arrays whole it reads the slots the engine was built with, whatever is
live; a step that follows the live lanes (as the KV write does:
``kv_write_lanes_mean``) would read them. A program whose ring has no such
field, or a model with no lane state (the field is 0), gives nothing."""


def read(run):
    lanes = [s["state_lanes"] for s in run.window_steps()
             if s["chunk"] > 0 and s.get("state_lanes")]
    return (sum(lanes) / len(lanes), len(lanes)) if lanes else None
