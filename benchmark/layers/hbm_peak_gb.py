"""`hbm_peak_gb` (device): the allocator's `peak_bytes_in_use` on the fullest
chip after the window.  On this machine it leaves out a running program's
temporaries (PERF.md section 7), so read it beside `hbm_program_gb`."""


def read(obs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in obs["run"]["devices"]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
