"""Model step: device time under the scopes `qkv` (q / kv projections and
RoPE) and `attn_out` (`wo` projection and residual) over the device's busy
time. Device trace, by the program's own scope names."""


def read(record):
    from benchlib import scope_reduce
    return scope_reduce.share(record, ("qkv", "attn_out"))
