"""setup_init_s: seconds of set-up spent building the engine and making
its state and the input pool (host clock, ended by block_until_ready)."""


def read(rec):
    return rec["setup_init_s"]
