"""The page-gather kernel (``ops/ragged_decode._gather_pages_pallas``) on the
device trace.

On the v5e at gpt2-large's sizes XLA puts the kernel's operand (one layer's
pool, staged by a ``copy``) and its result in the alternate memory space
``S(1)``, so the kernel's own events time an on-chip copy (12 us a call for
21 MB, my chip run, PR 24) and the HBM traffic of the gather is in the
``slice``/``copy``/``reshape`` around it. A share of the HBM roofline taken
over these events passes 100% (PERF.md, PR 24 finding 3), so there is none:
``page_gather_us_per_call.*`` reports the time, and ``step_hbm_roofline.*``
bounds the whole step. The PR that makes the kernel read HBM itself adds its
work function and a roofline reader as new files.
"""

#: The kernel's events on the device trace's "XLA Ops" line: a Mosaic custom
#: call whose result is the gathered view ``[entries, page_size, n_embd]``.
EVENTS = r"= \w+\[\d+,\d+,\d+\]\S* custom-call\(.*tpu_custom_call"
