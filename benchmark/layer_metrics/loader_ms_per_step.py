"""Host time the input pipeline takes per step: mean, over the window's
steps, of the clock between on_train_batch_end(k) and
on_train_batch_begin(k+1), which is the DataLoader fetching and collating
the next batch."""
LAYER, UNIT, SOURCE, MOVES = ("input pipeline", "ms", "host_clock",
                              "train_tokens_per_s_chip")


def read(obs):
    gaps = obs.get("loader_gaps_s")
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
