"""XLA compilations finished inside the measured window; should read 0."""


def read(obs):
    return obs["compiles"]
