"""The yardstick: manifest loading, the measured run, the checks behind
``correct``, operation counts, the peak table and the trace reduction.
Later PRs add data files and metric readers beside it and edit nothing here.
"""
