"""scatter_calls.train: the planned-scatter kernel calls one backward is
planned to make: the planner's ``train_plan_slices`` counter over the
window's batch, one per slice of samples whose dz fits the kernel's
VMEM. It is the plan's slice count, a function of the batch and the
kernel's ``MAX_DZ_VMEM_BYTES``, taken at set-up: nothing inside the
window moves it, and it does not count the calls a trace shows. Moves
``train_impressions_per_s``; read where the program counts it."""


def read(x):
    return x["counters"].get("scatter_calls")
