"""(max - min) / mean of batches completed per device
(``DevicePool.stats()``), in percent."""


def read(run):
    done = run.pool_completed
    if not done or not sum(done):
        return None
    return 100.0 * (max(done) - min(done)) / (sum(done) / len(done))
