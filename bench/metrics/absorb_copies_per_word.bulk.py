"""Words absorb writes on the host per delivered word: the tracer's
``absorb_words_copied`` counter (tenant buffers, leftover words and the
health monitor's sample) over the window's delivered words.  1.0 is one
host copy of each word after the device-to-host copy."""


def read(obs):
    st = obs["stages"]
    if not obs["words"] or "absorb_words_copied" not in st:
        return None
    return st["absorb_words_copied"] / obs["words"]
