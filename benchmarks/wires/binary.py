"""Binary wire: `ServiceClient.submit(binary=True)`; the client library
encodes the histories and ships one columnar frame."""


def send(client, histories, workload, consistency):
    rows = [[{"process": p, "type": t, "f": f, "value": v}
             for p, t, f, v in h] for h in histories]
    return client.submit(rows, workload=workload, consistency=consistency,
                         binary=True)
