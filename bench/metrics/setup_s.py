"""Set-up time: process start to the window's start (loading, weights,
compilation or cache loads, warm-up), host clock."""


def read(name, rec):
    return rec["setup_s"]
