"""Valid tokens of the requests that finished inside the window, a second:
the rate the stack prefills at (a request is a prompt; the family says how
long each is)."""

NAME = "tokens_per_s.bulk"
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "network"
MOVES = "videos_per_s"


def read(facts):
    import os
    lengths_of = getattr(facts.family, "prompt_lengths", None)
    if lengths_of is None:
        return None
    lengths = lengths_of(facts.config)
    return sum(lengths[os.path.basename(p)] for p, done in zip(
        facts.schedule.paths, facts.finished_in_window) if done) \
        / facts.seconds
