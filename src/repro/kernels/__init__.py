"""Device kernels of the scheduler.

``schedule_dp`` holds the level-synchronous schedule-DP sweeps (forward
start/finish times, backward tails) that the batched evaluator and the
device search engine use for exact evaluation: an XLA form and a Pallas
form of the same recursion.
"""
