"""Seconds from process start to the first due arrival: loading,
building the factors, compiling or loading every program, warm-up."""


def read(rec):
    return rec["setup_s"]
