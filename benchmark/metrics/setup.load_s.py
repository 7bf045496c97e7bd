"""setup.load_s: host clock from the protocol's build to the first
results of the init, chunk and check programs: the protocol's host
construction (lazy: its tables are built when the programs are first
traced), the compile or the compile cache's load, and one warm chunk on
a throwaway batch."""


def read(record):
    return record["setup"].get("load_s")
