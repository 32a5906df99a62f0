"""`hbm_program_gb` (device): bytes a device holds while the step program
runs, by the compiler's plan for the executable that ran: arguments +
outputs - aliased + temporaries (`memory_analysis()`)."""


def read(obs):
    b = obs["counters"].get("program_bytes")
    return b / 1e9 if b else None
