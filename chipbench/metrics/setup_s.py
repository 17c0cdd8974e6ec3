"""Process start to the window's opening: weights, engine or step,
compilation or cache loading, warm-up and pre-roll."""


def read(rec):
    return rec.setup_s
