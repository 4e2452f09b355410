"""Model step: the token-slots the softmax router sent to the experts
held here over all positions x k, summed over the layers of a step,
median over the window's steps; percent: `held_slots_share`'s reading of
the program's counters `moe_tokens_per_expert` and `moe_slots_elsewhere`,
under this cell's name. held / E of it (16 / 128) is an even share; a
quarter of the stream's positions carry the mask token's embedding and
choose alike in the first layer."""

from benchlib.spec import load_module

read = load_module("layer_metrics", "held_slots_share").read
