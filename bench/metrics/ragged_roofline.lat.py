"""Ragged query kernel (kernels/wcsd_query.py): the least time the chip
needs to read the label rows of the device-served queries, over the
kernel's device time, in %.

Work counted: for each point request the device answered (not from the
memo, not riding a duplicate) whose answer was delivered in the traced
part of the window, the label entries of its two rows, |L(s)| + |L(t)|, at 12
bytes each (int32 hub, distance, level). That is what the algorithm must
read; tile pairs, padding and DMAs are not counted, so a change to the
tiling cannot make the count stale. The bound is HBM bandwidth: the join
does no arithmetic worth a compute bound. Kernel time: the device time of
the ops named after the kernel. XLA names each launch of the ragged
query kernel's ``tpu_custom_call`` ``wcsd_query_ragged.<n>`` after its
Pallas function; the flush's other custom calls (``custom-call.<n>``:
gathers' bound hints, buffer allocation, concatenations) are not the
kernel and do not count. Profile requests run another kernel
(``wcsd_profile_ragged``), whose time is not counted here, so neither is
their work."""
KERNEL = "wcsd_query_ragged"
ENTRY_BYTES = 12


def is_kernel(name: str) -> bool:
    return name.split(".")[0] == KERNEL


def read(run):
    if run.trace is None or not run.peaks:
        return None
    secs = run.trace.op_seconds(is_kernel)
    if secs <= 0:
        return None
    dev = (~run.memo & ~run.dup & (run.deliver >= run.trace_from)
           & (run.deliver <= run.seconds))
    if run.kind is not None:
        dev &= run.kind == 0
    rows = run.row_entries[run.s[dev]] + run.row_entries[run.t[dev]]
    need = float(rows.sum()) * ENTRY_BYTES / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need / secs
