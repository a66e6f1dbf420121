"""Deploy to HEALTHY on the host clock: worker process, chip open,
weights, engine and its compiles."""


def read(obs):
    return obs.get("replica_start_s")
