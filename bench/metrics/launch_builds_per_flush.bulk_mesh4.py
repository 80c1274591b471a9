"""Sharded launch callables built per flush cycle in the window: the
tracer's ``launch_builds`` counter (a miss of the cache that keeps one
jitted ``shard_map`` per launch shape, gang or solo) over the front-end's
flushes.  0 when warm-up built every shape; a program without the counter
reports nothing."""


def read(obs):
    st = obs["stages"]
    if "launch_builds" not in st or not obs["flushes"]:
        return None
    return st["launch_builds"] / obs["flushes"]
