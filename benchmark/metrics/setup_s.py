"""End to end: process start to the open of the measured window — boot and
calibration, generating, loading, warm-up (arena builds, compiles or cache
reads), and any window that compiled and was therefore run again."""


def read(obs):
    return obs.setup_s
