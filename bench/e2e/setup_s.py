"""Seconds from the start of the process to the first timed campaign:
imports, device set-up, cohort generation and the warm-up campaign, with
any compilation it needs."""


def read(run):
    return run.setup_s
