"""Model step: the token-slots the group-limited router sent to the
experts held here over all tokens x k, summed over the expert layers of a
step, median over the window's steps; percent: `held_slots_share`'s
reading of the program's counters `moe_tokens_per_expert` and
`moe_slots_elsewhere`, under this cell's name. held / E of it (8 / 512)
is an even share; the held experts all lie in group 0 of 8, which a
token keeps or not as a whole."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "held_slots_share").read
