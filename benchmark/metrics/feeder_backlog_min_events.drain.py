"""Least (produced - asked for) the feeder saw at a fetch in the window, in
events.  0: a fetch found the topic empty, so the rate is the feeder's."""


def read(obs):
    return obs["feeder"].get("backlog_min")
