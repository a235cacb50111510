"""90th percentile of the time per output token in a cell below its knee:
per request (last token - first token) / (n - 1) over the requests due in
the window, from the part of the window before a capture began. Recorded,
not judged, because its spread from run to run is not one a bound can hold
(a bound has to lie between twice and eight times the spread):

- `serve-olmoe-chat`: a sparse model's decode step costs more the more
  slots are live (it reads the experts its live rows pick), so the tail
  follows the occupancy a seed draws: 5.6 % from seed to seed (PERF.md,
  PR 26).
- `serve-gpt2m-chat` (since PR 55; end to end as `tpot_p90_ms` until
  then): 135 requests of 0.05-0.8 s with ~1.2 slots live. The seed draws
  which requests overlap: one seed run twice agrees to 0.02-0.4 %, six
  seeds read 1.571-1.725 ms (6.7 %). And the machine stands still for
  ~0.12 s none to four times a window, each time putting the 1-5 requests
  then live into the far tail (+0.3-5 %). Under a schedule that is one
  draw rotated by the seed the seeds agree to 0.1 % and the stalls alone
  are left: 0.17 % in one of the driver's checks, 1.6-2.3 % in a call of
  this repo's (PERF.md section 6, PR 55; ROADMAP S0b(1))."""
from pbench import common


def read(v):
    return common.percentile(v.counters["tpot_ms"], 90)
