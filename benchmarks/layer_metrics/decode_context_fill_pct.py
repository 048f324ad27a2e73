"""Context tokens the decode dispatches had to attend over the tokens a
dispatch is sized for (engine.stats: decode_attended_tokens / (decode_calls
x max_slots x max_seq_len)): the useful share of a kernel that reads every
page of every slot."""

from benchmarks.layer_metrics import _readers


def read(run):
    attended = _readers.stat(run, "decode_attended_tokens")
    calls = _readers.stat(run, "decode_calls")
    if attended is None or not calls:
        return None
    sizes = run["config"]["engine"]
    return 100.0 * attended / (calls * sizes["max_slots"]
                               * sizes["max_seq_len"])
