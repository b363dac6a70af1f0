"""queries_per_s: the queries done inside the window, over the window's
length. A query still running when the window closes counts by the share
of its time that fell inside it, so the rate is of all the work of the
window and not a whole count that moves in steps of one query."""


def read(obs):
    if obs.loop != "closed":
        return None
    return obs.completed_in_window / obs.seconds
