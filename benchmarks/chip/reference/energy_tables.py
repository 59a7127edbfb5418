"""The paper's energy and device tables (EAFL Sec. 4.2), written out for the
plain references. Kept apart from the program on purpose: a reference
that imported the program's tables would agree with any error in them.

Table 2 device categories: 0 high-end (Huawei Mate 10), 1 mid-range
(Nexus 6P), 2 low-end (Huawei P9). Table 1 communication cost: battery %
per hour, ``a * hours + b``, rows WiFi / 3G, columns download / upload
(Kalic et al., MIPRO'12). Idle drain mixes a screen-off baseline with
interactive use.
"""
import numpy as np

POWER_W = np.array([6.33, 5.44, 2.98], np.float32)
PERF_PER_W = np.array([5.94, 4.03, 3.55], np.float32)
BATTERY_MAH = np.array([4000.0, 3450.0, 3000.0], np.float32)
NOMINAL_VOLTAGE = 3.85
COMM_A = np.array([[18.09, 21.24], [20.59, 15.31]], np.float32)
COMM_B = np.array([[0.17, -2.68], [-1.09, 2.67]], np.float32)
IDLE_POWER_W = 0.03
BUSY_POWER_W = 1.50
