"""The control, at a size a test run holds: the reference one precision
step below what the configuration states, put in the program's place,
must fail the cell's limits, and so must half of the batch left out.
The same readings at the cells' own sizes come from ``readings.py`` on
the chip."""
import readings
import tiny


def test_control_and_half_batch_fail_the_limits():
    out = readings.readings(tiny.CONFIG, tiny.TRAFFIC, 2**32 + 3)
    lim = tiny.LIMITS
    for name in ("control", "half_batch"):
        nums = out[name]
        failed = [k for k in ("loss_gap", "grad_gap", "change_gap")
                  if nums[k] > lim[k]]
        assert failed, (name, nums)
