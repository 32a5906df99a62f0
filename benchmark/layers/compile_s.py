"""`compile_s` (entry): host clock round the first call of each shape of the
run: seeded weights, the reference check's programs, the step.  Compilation
in a cold run, loading from the persistent cache in a warm one."""


def read(obs):
    return obs["counters"].get("compile_s")
