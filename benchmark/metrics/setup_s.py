"""setup_s: process start to the window's start (build, compile-cache
load, warm-up).  Host clock."""


def read(record):
    return record["setup"]["setup_s"]
