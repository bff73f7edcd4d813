def read(run):
    live, padded = run.counter("serve.live_requests"), run.counter("serve.padded_slots")
    return 100.0 * live / padded if padded else None
