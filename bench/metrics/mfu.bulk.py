"""The whole served path's share of the chips' peak FLOP/s, %: useful ANN
FLOPs per word (``bench/work.py``) times the words delivered per second
in the window, over chips times the peak."""


def read(obs):
    if not obs["words"] or obs.get("peaks") is None:
        return None
    flops = sum(n * obs["classes"][core]["flops_per_word"]
                for core, n in obs["words_by_core"].items())
    return 100.0 * flops / obs["window_s"] / (
        obs["chips"] * obs["peaks"]["flops_per_s"])
