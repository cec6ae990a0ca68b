"""The whole replay's share of the chips' bf16 peak: the profile's FLOPs
over the replay's mean wall time (host clock, whole window), per chip.  It
bounds every atom's roofline from above, whatever atoms a replay uses."""


def read(run):
    if run.peak is None or run.replay_s <= 0:
        return None
    per_replay = run.replay_s / run.replays
    return 100.0 * run.profile_flops / per_replay / run.peak["bf16_flops"]
