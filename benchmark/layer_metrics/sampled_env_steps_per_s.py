"""EnvRunner actors: delta of `num_env_steps_sampled_lifetime` over the
window. Program counter over the host clock."""


def read(record):
    sampled = record.get("counters", {}).get("sampled_env_steps")
    window = record.get("clock", {}).get("window_s")
    return sampled / window if sampled is not None and window else None
