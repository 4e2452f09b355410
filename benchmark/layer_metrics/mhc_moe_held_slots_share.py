"""Model step: the token-slots the sigmoid router sent to the experts held
here over all tokens x k, summed over the expert layers of a step, median
over the window's steps; percent: `held_slots_share`'s reading of the
program's counters `moe_tokens_per_expert` and `moe_slots_elsewhere`,
under this cell's name. held / E of it (8 / 64 = 12.5%) is an even share,
which the balanced choice bias gives."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "held_slots_share").read
